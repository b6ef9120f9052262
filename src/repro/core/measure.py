"""Measured-latency subsystem — the repo's stand-in for the paper's
compile-and-measure loop (Galen compiles each candidate policy with TVM
and times it on the ARM core; AMC found analytic proxies materially
mis-rank policies).

Three layers, bottom-up:

* **Unit measurement** (`measure_unit_rows`) — for every layer spec,
  build the *deploy-path* op the policy would actually execute
  (``deploy.quantize_weight`` container -> ``layers.materialize_weight``
  -> einsum; a gather for embeddings) in each weight container
  (raw / int8 / packed int4), time it with warmup + ``block_until_ready``
  fencing, and record measured seconds next to the analytic roofline term
  for the same (spec, container).

* **Calibration** (`fit_calibration` -> `CalibrationTable`) — per
  (layer kind, container) geometric-mean measured/analytic ratios, plus
  a lumped residual factor for the attention extras + dispatch overhead
  fitted from a whole-model measurement. The table is JSON-serialized as
  ``artifacts/latency_calibration.json`` (benchmarks/calibrate_oracle.py)
  and consumed by all three oracle forms via their ``calib=`` argument:
  the factors bake into the ``JaxBatchOracle`` trace as constants, so
  ``oracle_mode="calibrated"`` keeps the fused rollout at its
  single-dispatch bound.

* **Policy measurement** (`measure_policy`) — deploy a full search
  policy onto integer containers (per-unit-kind bit widths through
  ``quantize_params_for_deploy(bits_for=...)``) and wall-clock the jitted
  deployed forward. FIFO-memoized by the policy's container signature so
  ``oracle_mode="measured"`` re-times only distinct top-K candidates.

Deployment note: on scan-stacked models the per-layer weights share one
stacked array per name, so a policy deploys at the WIDEST container any
layer of that name asks for (conservative), and structured pruning is
not materialized — measured mode times the quantization decision, which
is the part the analytic oracle models per-container.
"""
from __future__ import annotations

import dataclasses
import json
import time
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.deploy import quantize_params_for_deploy, quantize_weight
from repro.core.latency import (CONTAINERS, HardwareTarget, LatencyContext,
                                V5E, container_for_bits, fifo_cached,
                                policy_latency, roofline_from_compiled,
                                unit_latency)
from repro.core.policy import Policy
from repro.core.spec import LayerCMP, LayerSpec, effective_bits

DEFAULT_CALIBRATION_PATH = "artifacts/latency_calibration.json"

# Container -> the LayerCMP whose analytic term the measurement is
# compared against (full width kept; the containers differ only in
# weight storage, which is exactly what the deploy path changes).
CONTAINER_BITS = {"raw": None, "int8": 8, "int4": 4}


def _container_cmp(spec: LayerSpec, container: str) -> LayerCMP:
    keep = spec.prune_dim if spec.prune_dim else 0
    if container == "raw":
        return LayerCMP(keep=keep, mode="FP32")
    if container == "int8":
        return LayerCMP(keep=keep, mode="INT8", w_bits=8, a_bits=8)
    return LayerCMP(keep=keep, mode="MIX", w_bits=4, a_bits=4)


@dataclass(frozen=True)
class MeasureConfig:
    warmup: int = 2
    repeats: int = 5
    tokens: int = 64          # rows fed to each unit op (the m dimension)
    seed: int = 0


def time_best(fn: Callable[[], object], warmup: int = 2,
              repeats: int = 5) -> float:
    """Best-of-N wall clock with warmup and ``block_until_ready`` fencing
    (best-of filters scheduler noise better than mean on shared CI)."""
    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


# ===========================================================================
# Unit measurement
# ===========================================================================

def _unit_dims(spec: LayerSpec) -> tuple:
    """(k, n) of the dense-equivalent matmul a unit executes on the
    deploy path. Convs are their im2col view; gated MLPs fold the
    up+gate matmuls into one widened n (same FLOPs/bytes the analytic
    unit charges)."""
    if spec.kind == "conv":
        k = int(round(spec.weight_elems / max(1, spec.out_dim)))
        return k, int(spec.out_dim)
    if spec.kind == "embed":
        return int(spec.in_dim), int(spec.out_dim)      # vocab rows, d cols
    k = int(spec.in_dim)
    return k, int(round(spec.weight_elems / max(1, k)))


def _unit_callable(spec: LayerSpec, container: str, m: int, key):
    """Jitted deploy-path op for one (spec, container): materialize the
    integer container and run the consuming op, exactly as
    ``models/layers.py`` does at serving time."""
    from repro.models.layers import materialize_weight

    k, n = _unit_dims(spec)
    kw, kx = jax.random.split(key)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    p = {"w": w} if container == "raw" \
        else quantize_weight(w, CONTAINER_BITS[container])
    if spec.kind == "embed":
        ids = jax.random.randint(kx, (m,), 0, k)
        fn = jax.jit(lambda p, i: jnp.take(
            materialize_weight(p, jnp.float32), i, axis=0))
        args = (p, ids)
    else:
        x = jax.random.normal(kx, (m, k), jnp.float32)
        fn = jax.jit(lambda p, x: x @ materialize_weight(p, x.dtype))
        args = (p, x)
    return lambda: fn(*args)


def measure_unit_rows(specs: Sequence[LayerSpec],
                      hw: HardwareTarget = V5E,
                      ctx: Optional[LatencyContext] = None,
                      cfg: MeasureConfig = MeasureConfig()) -> list:
    """Measured-vs-analytic rows per (unique unit shape, container).

    MoE expert stacks have no dense 2-D equivalent (analytic FLOPs count
    ``top_k`` active experts, storage counts all) and fall back to the
    1.0 factor — the skip is recorded as an explicit row so the artifact
    never silently reads as full coverage.
    """
    ctx = ctx or LatencyContext(tokens=cfg.tokens, seq_ctx=0, mode="prefill")
    mctx = dataclasses.replace(ctx, tokens=cfg.tokens)
    rows, seen = [], {}
    key = jax.random.PRNGKey(cfg.seed)
    for spec in specs:
        if spec.kind in ("moe_up", "moe_down"):
            rows.append({"kind": spec.kind, "name": spec.name,
                         "skipped": "stacked expert weights"})
            continue
        k, n = _unit_dims(spec)
        for container in CONTAINERS:
            if container == "int4" and k % 2:
                rows.append({"kind": spec.kind, "name": spec.name,
                             "container": container,
                             "skipped": "odd contraction dim"})
                continue
            sig = (spec.kind, k, n, container)
            if sig in seen:         # scan-stacked layers repeat shapes
                continue
            key, sub = jax.random.split(key)
            t = time_best(_unit_callable(spec, container, cfg.tokens, sub),
                          cfg.warmup, cfg.repeats)
            ana = unit_latency(spec, _container_cmp(spec, container),
                               1.0, hw, mctx).time_s
            seen[sig] = True
            rows.append({"kind": spec.kind, "name": spec.name,
                         "container": container, "k": k, "n": n,
                         "m": cfg.tokens, "measured_s": t,
                         "analytic_s": ana,
                         "ratio": t / ana if ana > 0 else float("inf")})
    return rows


def measure_kernel_rows(cfg: MeasureConfig = MeasureConfig(),
                        dims: tuple = (256, 256, 256)) -> list:
    """Informational rows timing the actual Pallas ``quant_matmul``
    int8/int4 kernels against the dense f32 matmul of the same shape.
    (The deployed forward uses the dequantize-into-matmul path measured
    above; these rows track the kernel alternative — in interpret mode
    on CPU they are orders of magnitude off real TPU numbers.)"""
    from repro.kernels import ops

    M, K, N = dims
    kx, kw = jax.random.split(jax.random.PRNGKey(cfg.seed))
    x = jax.random.normal(kx, (M, K), jnp.float32)
    w = jax.random.normal(kw, (K, N), jnp.float32)
    dense = jax.jit(lambda x, w: x @ w)
    rows = [{"kernel": "dense_f32", "M": M, "K": K, "N": N,
             "measured_s": time_best(lambda: dense(x, w),
                                     cfg.warmup, cfg.repeats)}]
    for bits, name in ((8, "quant_matmul_int8"), (4, "quant_matmul_int4")):
        t = time_best(lambda: ops.quantized_matmul(x, w, w_bits=bits),
                      cfg.warmup, cfg.repeats)
        rows.append({"kernel": name, "M": M, "K": K, "N": N,
                     "measured_s": t})
    return rows


# ===========================================================================
# Calibration table
# ===========================================================================

@dataclass
class CalibrationTable:
    """Measured/analytic correction factors, keyed (kind, container).

    ``ratios[kind][container]`` scales that unit's roofline term;
    ``extra["attn"]`` scales the attention score/AV + KV-cache extras and
    ``extra["overhead"]`` the per-op dispatch overhead (both lumped
    residuals from a whole-model fit). Unknown kinds/containers fall back
    to 1.0, so a partial table degrades to the analytic oracle.
    """
    ratios: dict
    extra: dict = field(default_factory=dict)
    meta: dict = field(default_factory=dict)

    def factor(self, kind: str, container: str) -> float:
        return float(self.ratios.get(kind, {}).get(container, 1.0))

    def extra_factor(self) -> float:
        return float(self.extra.get("attn", 1.0))

    def overhead_factor(self) -> float:
        return float(self.extra.get("overhead", 1.0))

    def unit_factors(self, specs: Sequence[LayerSpec]) -> np.ndarray:
        """(L, 3) per-spec factors in ``latency.CONTAINERS`` column
        order — the array the batch oracles index by container bucket."""
        out = np.ones((len(specs), len(CONTAINERS)), np.float64)
        for i, s in enumerate(specs):
            for j, c in enumerate(CONTAINERS):
                out[i, j] = self.factor(s.kind, c)
        return out

    def to_dict(self) -> dict:
        return {"ratios": self.ratios, "extra": self.extra, "meta": self.meta}

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)

    @classmethod
    def from_dict(cls, d: dict) -> "CalibrationTable":
        return cls(ratios=d.get("ratios", {}), extra=d.get("extra", {}),
                   meta=d.get("meta", {}))

    @classmethod
    def load(cls, path: str) -> "CalibrationTable":
        """Read a table; refuse one fitted on another backend, whose
        factors describe a different machine (a table with no recorded
        backend is taken as it is)."""
        with open(path) as f:
            table = cls.from_dict(json.load(f))
        fitted, running = table.meta.get("backend"), jax.default_backend()
        if fitted is not None and fitted != running:
            raise ValueError(
                f"calibration table {path!r} was fitted on backend "
                f"{fitted!r}, but JAX is running on {running!r}; refit it "
                f"here with `python -m benchmarks.calibrate_oracle` or "
                f"use oracle_mode='analytic'")
        return table


def load_calibration(path: Optional[str] = None) -> CalibrationTable:
    """Load the committed calibration artifact (default path relative to
    the repo root / benchmark cwd)."""
    try:
        return CalibrationTable.load(path or DEFAULT_CALIBRATION_PATH)
    except FileNotFoundError:
        raise FileNotFoundError(
            f"calibration artifact not found at "
            f"{path or DEFAULT_CALIBRATION_PATH!r} — generate it with "
            f"`python -m benchmarks.calibrate_oracle` or pass calib= "
            f"explicitly") from None


def fit_calibration(unit_rows: Sequence[dict],
                    meta: Optional[dict] = None) -> CalibrationTable:
    """Geometric-mean measured/analytic ratio per (kind, container)."""
    logs: dict = {}
    for r in unit_rows:
        if "ratio" not in r or not np.isfinite(r["ratio"]) or r["ratio"] <= 0:
            continue
        logs.setdefault(r["kind"], {}).setdefault(
            r["container"], []).append(np.log(r["ratio"]))
    ratios = {k: {c: float(np.exp(np.mean(v))) for c, v in d.items()}
              for k, d in logs.items()}
    return CalibrationTable(ratios=ratios, meta=meta or {})


def fit_extra_factor(table: CalibrationTable, specs: Sequence[LayerSpec],
                     ref_policy: Policy, measured_total_s: float,
                     hw: HardwareTarget, ctx: LatencyContext,
                     window: int = 0) -> None:
    """Fit the lumped attention/overhead residual in place: whatever the
    whole-model measurement shows beyond the calibrated unit terms is
    attributed to the extras (attention score/AV, norms, dispatch).
    Existing extra factors are reset first so the fit is computed
    against unit-factor extras — refitting is idempotent."""
    table.extra["attn"] = table.extra["overhead"] = 1.0
    pl = policy_latency(specs, ref_policy, hw, ctx, window, calib=table)
    unit_s = sum(u.time_s for u in pl.units if not u.name.endswith(".attn"))
    extra_s = sum(u.time_s for u in pl.units if u.name.endswith(".attn"))
    extra_s += pl.overhead_s
    if extra_s > 0:
        f = max(0.0, (measured_total_s - unit_s)) / extra_s
        table.extra["attn"] = f
        table.extra["overhead"] = f


# ===========================================================================
# Whole-policy deployment + measurement
# ===========================================================================

def spec_param_names(spec: LayerSpec) -> tuple:
    """Param-tree weight names a spec's policy decision governs (the
    names ``quantize_params_for_deploy`` keys containers by)."""
    k = spec.kind
    if k == "embed":
        return ("embed",)
    if k == "head":
        return ("unembed", "head")
    if k == "attn_qkv":
        return ("wq", "wk", "wv")
    if k == "attn_out":
        return ("wo",)
    if k == "mlp_up":
        return ("dense_w_up", "dense_w_gate") \
            if spec.extra.get("dense_residual") else ("w_up", "w_gate")
    if k == "mlp_down":
        return ("dense_w_down",) \
            if spec.extra.get("dense_residual") else ("w_down",)
    if k == "moe_up":
        return ("w_up", "w_gate")
    if k == "moe_down":
        return ("w_down",)
    if k == "ssm_in":
        return ("in_proj",)
    if k == "ssm_out":
        return ("out_proj",)
    if k == "rglru_in":
        return ("w_x", "w_y")
    if k == "rglru_out":
        return ("w_out",)
    if k == "conv":
        return ("stem", "conv1", "conv2", "skip")
    return ()


def policy_bits_by_name(specs: Sequence[LayerSpec],
                        policy: Policy) -> dict:
    """Weight name -> deployed bit width (>8 = raw). Scan-stacked models
    share one array per name across layers, so the WIDEST width any
    layer asks for wins — deployment never quantizes a layer harder than
    its policy allows."""
    bits: dict = {}
    for s, c in zip(specs, policy.cmps):
        wb, _ = effective_bits(c)
        for name in spec_param_names(s):
            bits[name] = max(bits.get(name, 0), int(wb))
    return bits


def deploy_policy_params(cmodel, policy: Policy):
    """Materialize a search policy's quantization decisions as real
    integer weight containers on the model's params."""
    bits = policy_bits_by_name(cmodel.specs, policy)
    return quantize_params_for_deploy(cmodel.params,
                                      bits_for=lambda n: bits.get(n))


def _deployed_forward(cmodel):
    """(fn(qp, batch), batch-arg extractor) for the deployed forward of
    an LM or ResNet compressible model."""
    cfg = cmodel.cfg
    if hasattr(cfg, "vocab_size"):
        from repro.models import model as M
        return lambda qp, batch: M.forward(cfg, qp, tokens=batch["tokens"])
    from repro.models import resnet as R
    return lambda qp, batch: R.forward(cfg, qp, batch["images"])


_measure_memo: dict = {}
_MEASURE_MEMO_MAX = 32


def measure_policy(cmodel, policy: Policy, batch: dict,
                   cfg: MeasureConfig = MeasureConfig()) -> float:
    """Wall-clock seconds of the jitted deployed forward under
    ``policy``'s containers. FIFO-memoized on (model params, batch,
    container signature): ``oracle_mode="measured"`` re-times only
    distinct top-K candidates, and repeated winners are free."""
    bits = policy_bits_by_name(cmodel.specs, policy)
    sig = tuple(sorted((n, container_for_bits(b)) for n, b in bits.items()))
    key = (id(cmodel.params), id(batch), sig, cfg)

    def factory():
        qp = quantize_params_for_deploy(cmodel.params,
                                        bits_for=lambda n: bits.get(n))
        fwd = jax.jit(_deployed_forward(cmodel))
        t = time_best(lambda: fwd(qp, batch), cfg.warmup, cfg.repeats)
        # hold refs so the identity key can't be recycled under us
        return (cmodel.params, batch, t)

    hit = fifo_cached(_measure_memo, _MEASURE_MEMO_MAX, key,
                      lambda h: h[0] is cmodel.params and h[1] is batch,
                      factory)
    return hit[2]


def measure_model_row(cmodel, batch: dict, container: str,
                      cfg: MeasureConfig = MeasureConfig()) -> dict:
    """Whole-model deployed-forward measurement for a uniform container,
    with ``roofline_from_compiled`` cost extraction on the compiled
    artifact. Deploys through ``uniform_policy`` so the measurement and
    the calibrated oracle's prediction describe the same containers
    (mix-unsupported embed/head ride int8 in the "int4" row)."""
    qp = cmodel.params if container == "raw" else deploy_policy_params(
        cmodel, uniform_policy(cmodel.specs, container))
    fwd = jax.jit(_deployed_forward(cmodel))
    compiled = fwd.lower(qp, batch).compile()
    t = time_best(lambda: fwd(qp, batch), cfg.warmup, cfg.repeats)
    rep = roofline_from_compiled(compiled)
    return {"container": container, "measured_s": t,
            "roofline": rep.summary()}


def uniform_policy(specs: Sequence[LayerSpec], container: str) -> Policy:
    """Uniform-quantization policy matching ``measure_model_row``'s
    deployment: INT8 everywhere for "int8"; 4-bit MIX where supported
    (INT8 on mix-unsupported embed/head) for "int4"."""
    pol = Policy.reference(specs)
    if container == "raw":
        return pol
    for s, c in zip(specs, pol.cmps):
        if not s.quantizable:
            continue
        if container == "int8" or not s.mix_supported:
            c.mode, c.w_bits, c.a_bits = "INT8", 8, 8
        else:
            c.mode, c.w_bits, c.a_bits = "MIX", 4, 4
    return pol
