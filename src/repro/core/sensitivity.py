"""Sensitivity analysis (paper Eq. 5, generalized ZeroQ) — fused.

For each layer and each probe CMP, compress ONLY that layer (reference
policy elsewhere) and measure the KL divergence between the compressed and
the original model's output distributions over N calibration samples:

    Ω(P) = 1/N Σ_j D_KL( M_P(θ;x_j) || M(θ;x_j) )

The full analysis runs once, up-front, for all layers (paper §Sensitivity);
results feed the agent state.

Every probe CMP is **legalized** first (``constraints.legalize`` — the
paper's TVM/ARM fallback rule): prune probes are rounded to the hardware
granularity via ``round_keep`` and quant probes fall back to INT8 where
``mix_allowed`` is False, so the KL features always describe policies the
agent can actually reach.

The probe evaluation itself is ONE jit execution per ``run_sensitivity``
call: all layer×probe single-layer policies are stacked into batched
(P, L) cspec arrays (the same traced-cspec builders
``accuracy_policy_batch`` shares — see ``compress.cspec_builder``), the
reference log-probs and every probe's KL are computed inside one
``jit`` whose probe loop is a ``lax.scan`` over vmapped probe blocks
(chunked to bound the live log-prob memory), and the (P,) KLs are
reduced on-device before the single host readback. ``run_sensitivity``
and ``full_sweep`` are both thin views over this fused core;
``run_sensitivity_sequential`` keeps the original one-dispatch-per-probe
path as the parity reference (mirroring the numpy-engine pattern of the
rollout engines), property-tested to ≤ 1e-6 per layer×probe KL in
``tests/test_sensitivity.py``.

Results are memoized per (cmodel, batch, params) identity, so every
engine constructor — and every member of a ``PopulationSearch`` built on
a common model — shares one analysis instead of re-running it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.constraints import legalize
from repro.core.latency import fifo_cached
from repro.core.policy import (Policy, PolicyBatch, policies_from_batch,
                               stack_policies)
from repro.core.spec import LayerCMP, LayerSpec, effective_bits


def kl_divergence(logp_c: jnp.ndarray, logp_o: jnp.ndarray) -> jnp.ndarray:
    """D_KL(compressed || original) averaged over batch (and positions)."""
    p_c = jnp.exp(logp_c)
    kl = jnp.sum(p_c * (logp_c - logp_o), axis=-1)
    return jnp.mean(kl)


# probe CMPs per method (paper: a predefined number of sample policies)
QUANT_W_PROBES = (8, 6, 4, 3, 2)
QUANT_A_PROBES = (8, 6, 4, 3, 2)
N_PRUNE_PROBES = 10

# the fixed probe set feeding the agent state (see SensitivityResult)
FEATURE_W_PROBES = (4, 2)
FEATURE_A_PROBES = (4, 2)
FEATURE_PRUNE_FRACS = (0.5, 0.25)
FEATURE_PROBES = ("w4", "w2", "a4", "a2", "p50", "p25")

# Legality-aware sentinel for probes that were never run (layer not
# quantizable / not prunable): a probed-and-robust layer reads 0.0
# (log1p(0)), an unprobed one reads MISSING_KL — the agent can tell
# "cannot be quantized" from "perfectly insensitive to quantization".
MISSING_KL = -1.0


@dataclass
class SensitivityResult:
    """per layer-spec name -> {probe_name: KL}"""
    table: Dict[str, Dict[str, float]]

    def feature(self, name: str, probe: str,
                default: float = MISSING_KL) -> float:
        """Raw KL for one probe; missing probes default to the
        ``MISSING_KL`` sentinel, consistent with ``feature_row``."""
        return self.table.get(name, {}).get(probe, default)

    def feature_row(self, name: str) -> np.ndarray:
        """(len(FEATURE_PROBES),) f32 probe features for one layer:
        log1p-squashed KLs, ``MISSING_KL`` where the probe was not run
        (not quantizable / not prunable — NOT the same as KL 0)."""
        row = self.table.get(name, {})
        return np.asarray(
            [np.log1p(row[k]) if k in row else MISSING_KL
             for k in FEATURE_PROBES], np.float32)

    def feature_rows(self, names: Sequence[str]) -> np.ndarray:
        """(len(names), len(FEATURE_PROBES)) array-form feature block —
        the form the state builders consume."""
        return np.stack([self.feature_row(n) for n in names])

    def features_for(self, name: str) -> List[float]:
        """Fixed-length probe feature vector for the agent state."""
        return [float(x) for x in self.feature_row(name)]


# ===========================================================================
# Probe plan: legalized layer×probe policies as stacked (P, L) arrays
# ===========================================================================

@dataclass(frozen=True)
class ProbeEntry:
    """One layer×probe row of a plan (bookkeeping for the result views)."""
    spec_idx: int
    layer: str
    method: str                # quant_w | quant_a | prune
    param: float               # bits (quant) or kept fraction (prune)
    tag: str                   # feature key, e.g. "w4" / "p50"


@dataclass
class ProbePlan:
    """All probes of one analysis in array form: row p of the (P, L)
    arrays is the reference policy with column ``entries[p].spec_idx``
    replaced by the **legalized** probe CMP (effective bits)."""
    entries: List[ProbeEntry]
    keep: np.ndarray           # (P, L) f64
    w_bits: np.ndarray         # (P, L) f64
    a_bits: np.ndarray         # (P, L) f64
    ref: Tuple[np.ndarray, np.ndarray, np.ndarray]   # (L,) each

    def __len__(self) -> int:
        return len(self.entries)


def build_probe_plan(specs: Sequence[LayerSpec],
                     w_probes: Sequence[int] = FEATURE_W_PROBES,
                     a_probes: Sequence[int] = FEATURE_A_PROBES,
                     prune_fracs: Sequence[float] = FEATURE_PRUNE_FRACS
                     ) -> ProbePlan:
    """Enumerate the layer×probe single-layer policies, each routed
    through ``legalize`` so the plan only contains reachable CMPs:
    probed keep counts obey ``round_keep`` (granularity-aligned, one
    granule floor) and MIX bit asks on ``mix_allowed``-False layers
    become the INT8 fallback instead of an illegal sub-8-bit policy."""
    ref_pb = stack_policies(specs, [Policy.reference(specs)])
    ref = (ref_pb.keep[0], ref_pb.w_bits[0], ref_pb.a_bits[0])
    entries: List[ProbeEntry] = []
    rows: List[Tuple[float, float, float]] = []

    def add(i: int, cmp: LayerCMP, method: str, param, tag: str):
        cmp = legalize(specs[i], cmp)
        w, a = effective_bits(cmp)
        entries.append(ProbeEntry(i, specs[i].name, method, param, tag))
        rows.append((float(cmp.keep), float(w), float(a)))

    for i, s in enumerate(specs):
        if s.quantizable:
            for b in w_probes:
                add(i, LayerCMP(keep=s.prune_dim, mode="MIX",
                                w_bits=int(b), a_bits=32),
                    "quant_w", b, f"w{int(b)}")
            for b in a_probes:
                add(i, LayerCMP(keep=s.prune_dim, mode="MIX",
                                w_bits=32, a_bits=int(b)),
                    "quant_a", b, f"a{int(b)}")
        if s.prunable and s.prune_dim:
            for frac in prune_fracs:
                add(i, LayerCMP(keep=max(1, int(s.prune_dim * float(frac)))),
                    "prune", float(frac),
                    f"p{int(round(float(frac) * 100))}")

    P, L = len(entries), len(specs)
    keep = np.tile(ref[0], (P, 1))
    wb = np.tile(ref[1], (P, 1))
    ab = np.tile(ref[2], (P, 1))
    for p, (e, row) in enumerate(zip(entries, rows)):
        keep[p, e.spec_idx], wb[p, e.spec_idx], ab[p, e.spec_idx] = row
    return ProbePlan(entries, keep, wb, ab, ref)


_plan_cache: dict = {}
_PLAN_CACHE_MAX = 256


def feature_probe_plan(specs: Sequence[LayerSpec]) -> ProbePlan:
    """The fixed agent-state probe plan, cached per spec-list identity."""
    hit = fifo_cached(
        _plan_cache, _PLAN_CACHE_MAX, id(specs),
        lambda h: h[0] is specs,
        lambda: (specs, build_probe_plan(specs)))
    return hit[1]


# ===========================================================================
# Fused core: every probe KL + the reference in ONE jit execution
# ===========================================================================

def _fused_kl_fn(cmodel, batch):
    """The jitted fused program, cached per (batch, params) identity on
    the adapter (same pattern as ``accuracy_policy_fn``'s cache —
    swapping in new weights must re-trace, since the traced builder
    bakes params and prune scores in as constants).

    Signature: ``(params, ref_k, ref_w, ref_a, keep, wb, ab) -> (P,)
    KLs`` with the probe arrays pre-chunked to (n_chunks, C, L); the
    weights are an argument so the program does not embed them. The
    reference log-probs are computed inside the same trace; the probe
    loop is a ``lax.scan`` over chunks of C vmapped probes, so peak live
    memory is C probe log-prob blocks, never P.
    """
    cached = getattr(cmodel, "_sens_kl_cache", None)
    if cached is not None and cached[0] is batch \
            and cached[1] is cmodel.params:
        return cached[2]
    build = cmodel.cspec_builder()

    def fused(params, ref_k, ref_w, ref_a, keep, wb, ab):
        def logp(k, w, a):
            return cmodel.log_probs(batch, build(k, w, a), params)

        logp_o = logp(ref_k, ref_w, ref_a)

        def chunk(_, xs):
            k, w, a = xs
            return None, jax.vmap(
                lambda kk, ww, aa: kl_divergence(logp(kk, ww, aa),
                                                 logp_o))(k, w, a)

        _, kls = jax.lax.scan(chunk, None, (keep, wb, ab))
        return kls.reshape(-1)

    fn = jax.jit(fused)
    cmodel._sens_kl_cache = (batch, cmodel.params, fn)
    return fn


def _fused_dispatch(fn, *args):
    """Indirection for the compiled fused program — the benchmark's
    ``sensitivity_dispatch_probe`` wraps this to count real executions
    (the 1-per-analysis acceptance bound)."""
    return fn(*args)


def _seq_eval(fn, cspec):
    """Indirection for the sequential path's per-probe evaluations —
    wrapped as a canary by the dispatch probe (a fused analysis must
    never fall back to per-probe dispatches)."""
    return fn(cspec)


def _plan_kls(cmodel, batch, plan: ProbePlan, chunk: int) -> np.ndarray:
    """(P,) probe KLs for a plan — ONE jit execution, one readback.

    Legalization can collapse distinct probes onto one policy (all four
    quant probes of a ``mix_allowed``-False layer become the same INT8
    row), so identical rows are evaluated once and the KLs fanned back
    out. The unique rows are padded to a chunk multiple with reference
    rows (KL 0) so the scan consumes equal blocks; padding is dropped
    on the host."""
    P, L = plan.keep.shape
    if P == 0:
        return np.zeros((0,), np.float64)
    rows = np.concatenate([plan.keep, plan.w_bits, plan.a_bits], axis=1)
    uniq, inverse = np.unique(rows, axis=0, return_inverse=True)
    U = uniq.shape[0]
    chunk = max(1, min(int(chunk), U))
    pad = (-U) % chunk

    def prep(arr: np.ndarray, ref_row: np.ndarray) -> jnp.ndarray:
        if pad:
            arr = np.concatenate([arr, np.tile(ref_row, (pad, 1))])
        return jnp.asarray(arr.reshape(-1, chunk, L), jnp.int32)

    fn = _fused_kl_fn(cmodel, batch)
    ref = tuple(jnp.asarray(r, jnp.int32) for r in plan.ref)
    kls = _fused_dispatch(fn, cmodel.params, *ref,
                          prep(uniq[:, :L], plan.ref[0]),
                          prep(uniq[:, L:2 * L], plan.ref[1]),
                          prep(uniq[:, 2 * L:], plan.ref[2]))
    return np.asarray(kls, np.float64)[:U][inverse.reshape(-1)]


def _result_from_plan(specs, plan: ProbePlan,
                      kls: np.ndarray) -> SensitivityResult:
    table: Dict[str, Dict[str, float]] = {s.name: {} for s in specs}
    for e, kl in zip(plan.entries, kls):
        table[e.layer][e.tag] = float(kl)
    return SensitivityResult(table)


# ===========================================================================
# Public views over the fused core
# ===========================================================================

_MEMO_CACHE_MAX = 8                    # per adapter instance
DEFAULT_CHUNK = 8


def run_sensitivity(cmodel, batch, chunk: int = DEFAULT_CHUNK,
                    memo: bool = True) -> SensitivityResult:
    """The agent-state analysis: legalized feature probes for every
    layer, evaluated as ONE jit execution (see the module docstring).

    ``cmodel``: CompressibleLM/CompressibleResNet; ``batch``:
    calibration data. ``memo=True`` (default) shares the result across
    callers with the same (cmodel, batch, params) identity — e.g. every
    engine constructor of a population built on one model. The memo
    lives ON the adapter (like ``_sens_kl_cache``), so it cannot extend
    the lifetime of models the caller has dropped.
    """
    plan = feature_probe_plan(cmodel.specs)

    def compute():
        kls = _plan_kls(cmodel, batch, plan, chunk)
        return (batch, cmodel.params,
                _result_from_plan(cmodel.specs, plan, kls))

    if not memo:
        return compute()[2]
    cache = getattr(cmodel, "_sens_memo", None)
    if cache is None:
        cache = cmodel._sens_memo = {}
    hit = fifo_cached(
        cache, _MEMO_CACHE_MAX, id(batch),
        lambda h: h[0] is batch and h[1] is cmodel.params,
        compute)
    return hit[2]


def run_sensitivity_sequential(cmodel, batch) -> SensitivityResult:
    """Parity reference: the same legalized probe plan, evaluated one
    jit dispatch per probe through the HOST cspec builder
    (``build_cspec``) — the original L×probe path. Kept (like the numpy
    rollout engines) purely so property tests can pin the fused core
    to it; production callers use ``run_sensitivity``.
    """
    plan = feature_probe_plan(cmodel.specs)
    kls = _plan_kls_sequential(cmodel, batch, plan)
    return _result_from_plan(cmodel.specs, plan, kls)


def _seq_logprobs_fn(cmodel, batch):
    """The sequential path's jitted log-probs, cached per
    (batch, params) identity like ``_fused_kl_fn`` — a fresh ``jax.jit``
    wrapper per call would defeat jit's callable-keyed cache and make
    every analysis (and every benchmark repeat) pay a re-trace."""
    cached = getattr(cmodel, "_sens_seq_cache", None)
    if cached is not None and cached[0] is batch \
            and cached[1] is cmodel.params:
        return cached[2]
    fn = jax.jit(lambda p, cs: cmodel.log_probs(batch, cs, p))
    cmodel._sens_seq_cache = (batch, cmodel.params, fn)
    return fn


def _plan_kls_sequential(cmodel, batch, plan: ProbePlan) -> np.ndarray:
    specs = cmodel.specs
    jit_lp = _seq_logprobs_fn(cmodel, batch)
    lp = lambda cs: jit_lp(cmodel.params, cs)
    logp_o = _seq_eval(lp, cmodel.build_cspec(Policy.reference(specs)))
    pols = policies_from_batch(specs, PolicyBatch(
        keep=plan.keep, w_bits=plan.w_bits, a_bits=plan.a_bits))
    out = np.empty(len(pols), np.float64)
    for p, pol in enumerate(pols):
        logp_c = _seq_eval(lp, cmodel.build_cspec(pol))
        out[p] = float(kl_divergence(logp_c, logp_o))
    return out


def full_sweep(cmodel, batch, w_bits=QUANT_W_PROBES, a_bits=QUANT_A_PROBES,
               n_prune: int = N_PRUNE_PROBES,
               chunk: int = DEFAULT_CHUNK) -> List[dict]:
    """Dense sweep used for the paper's Fig. 6 plots — a thin view over
    the same fused core as ``run_sensitivity`` (one jit execution for
    the whole layer×probe grid), with every probe legalized the same
    way."""
    plan = build_probe_plan(
        cmodel.specs, w_probes=w_bits, a_probes=a_bits,
        prune_fracs=tuple(float(f) for f in np.linspace(0.1, 1.0, n_prune)))
    kls = _plan_kls(cmodel, batch, plan, chunk)
    return [{"layer": e.layer, "method": e.method, "param": e.param,
             "kl": float(kl)} for e, kl in zip(plan.entries, kls)]
