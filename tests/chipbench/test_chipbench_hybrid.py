"""The hybrid Mamba-2 / attention family against the program: the plain
reference's logits against the program's forward at the smoke size,
uncompressed and under seeded legal policies; the family's units and
oracle against the program's specs and latency at the published
widths; and the ``stage_ms.ssd`` / ``stage_ms.mamba`` readers on a
synthetic trace."""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, stages
from chipbench import trace as T
from chipbench.families import hybrid as fam
from chipbench.reference import hybrid as ref
from chipbench.reference import oracle as ref_oracle
from chipbench.reference.compression import effective_bits

CELL = "granite-4.0-h-micro.search-pq-2x512"


def smoke_cfg() -> dict:
    """The configuration file at the program's smoke size
    (``configs/granite_4_0_h_micro.SMOKE``): [ssm, attn, ssm] x 2 at
    d 64, float32 compute."""
    from repro.models.registry import get_config
    sm = get_config("granite-4.0-h-micro", smoke=True)
    cfg = harness.load_cell(CELL)["config"]
    cfg.update(
        hidden_size=sm.d_model, num_hidden_layers=sm.num_layers,
        num_attention_heads=sm.num_heads, num_key_value_heads=sm.num_kv_heads,
        shared_intermediate_size=sm.d_ff, vocab_size=sm.vocab_size,
        layer_types=["attention" if k == "attn" else "mamba"
                     for k in sm.layer_kinds],
        attention_multiplier=sm.attention_multiplier,
        mamba_d_head=sm.ssm.head_dim, mamba_d_state=sm.ssm.d_state,
        mamba_chunk_size=sm.ssm.chunk_size, compute_dtype="float32")
    cfg["weights"]["branch_out_scale"] = 0.5
    return cfg


def _policies(specs, n, seed):
    from repro.core.policy import Policy, map_actions, stack_policies
    rng = np.random.default_rng(seed)
    pols = [Policy([map_actions(s, rng.random(3), "pq") for s in specs])
            for _ in range(n)]
    return stack_policies(specs, pols)


def test_smoke_config_is_the_registered_one():
    from repro.models.registry import get_config
    sm = get_config("granite-4.0-h-micro", smoke=True)
    got = fam.arch_config(smoke_cfg())
    for f in ("num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "vocab_size", "layer_kinds", "ssm",
              "position_embedding", "attention_multiplier", "norm_eps",
              "embedding_multiplier", "residual_multiplier",
              "logits_scaling", "tie_embeddings"):
        assert getattr(got, f) == getattr(sm, f), f


def test_program_logits_match_the_reference():
    from repro.core.compress import CompressibleLM
    cfg = smoke_cfg()
    params = jax.jit(lambda k: ref.make_params(cfg, k))(
        jax.random.PRNGKey(3))
    cm = CompressibleLM(fam.arch_config(cfg), params)
    assert [s.name for s in cm.specs] == ref.unit_names(cfg)
    # 40 tokens: five 8-token SSD chunks in the program, one recurrence
    # in the reference
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0,
                              cfg["vocab_size"])
    batch = {"tokens": toks}
    want = np.asarray(ref.logits(cfg, params, toks, None))
    got = np.asarray(cm.logits(batch))
    scale = np.abs(want).max()
    # float32 on both sides: the chunked scan against the step-by-step
    # recurrence differs in summation order only (~1e-7 of the largest
    # logit here); a mixer equation out of place moves them by O(1)
    assert np.abs(got - want).max() < 1e-5 * scale

    pb = _policies(cm.specs, 2, seed=7)
    assert (np.asarray(pb.w_bits) < 32).any()
    # at d 64 no unit has two MXU granules to keep one of, so a legal
    # policy prunes nothing: a third policy keeps seeded counts of heads
    # and channels off the grid, which the masks take all the same
    dims = np.asarray([s.prune_dim for s in cm.specs])
    rng = np.random.default_rng(8)
    keeps = np.where(dims > 0, rng.integers(1, np.maximum(dims, 1) + 1),
                     0)
    assert (keeps < dims).sum() >= 10
    rows = [(pb.keep[k], pb.w_bits[k], pb.a_bits[k]) for k in range(2)]
    rows.append((keeps, pb.w_bits[0], pb.a_bits[0]))
    build = cm.cspec_builder()
    for k, row in enumerate(rows):
        keep, wb, ab = (jnp.asarray(x, jnp.int32) for x in row)
        got = np.asarray(cm.logits(batch, build(keep, wb, ab)))
        want = np.asarray(ref.logits(
            cfg, params, toks,
            ref.policy_arrays(cfg, *(x.astype(jnp.float32)
                                     for x in (keep, wb, ab)))))
        # the same pruned channels and quantization grids: the policies
        # move the median logit by ~5e-4 of the largest, the two sides
        # agree to ~1e-7; a value within rounding of a grid step may
        # floor to the neighbouring level on one side only, so the worst
        # gap is bounded by a few 8-bit steps, the median tightly
        gap = np.abs(got - want)
        assert np.median(gap) < 1e-6 * scale, k
        assert gap.max() < 1e-3 * scale, k


def test_recurrent_tokens_follow_the_full_forward():
    cfg = smoke_cfg()
    params = jax.jit(lambda k: ref.make_params(cfg, k))(
        jax.random.PRNGKey(5))
    toks = ref.greedy_tokens(cfg, params, jax.random.PRNGKey(6), 24,
                             [1.0, 0.0])
    lg = ref.logits(cfg, params, toks, None)[:, :-1]
    hit = np.asarray(jnp.argmax(lg, -1) == toks[:, 1:]).mean(-1)
    assert hit[0] == 1.0 and hit[1] < 0.2


def test_family_units_and_oracle_match_the_program():
    from repro.core.compress import lm_layer_specs
    from repro.core.latency import (HardwareTarget, LatencyContext,
                                    policy_latency)
    from repro.core.policy import Policy, map_actions
    cell = harness.load_cell(CELL)
    cfg, tr = cell["config"], cell["traffic"]
    specs = lm_layer_specs(fam.arch_config(cfg))
    assert [s.name for s in specs] == fam.unit_names(cfg)
    assert len(specs) == 82
    us = fam.oracle_units(cfg)
    assert [u["name"] for u in us] == fam.unit_names(cfg)
    hw = HardwareTarget(**tr["oracle_hw"])
    lctx = LatencyContext(**tr["latency_context"])
    rng = np.random.default_rng(11)
    for _ in range(6):
        pol = Policy([map_actions(s, rng.random(3), "pq") for s in specs])
        keep = np.asarray([c.keep for c in pol.cmps], np.float64)
        bits = [effective_bits(c.mode, c.w_bits, c.a_bits)
                for c in pol.cmps]
        wb = np.asarray([b[0] for b in bits], np.float64)
        ab = np.asarray([b[1] for b in bits], np.float64)
        assert ref_oracle.illegal_units(us, keep, wb, ab) == 0
        want = ref_oracle.latency(us, keep, wb, ab, tr["oracle_hw"],
                                  tr["latency_context"])
        got = policy_latency(specs, pol, hw, lctx).total_s
        assert abs(got / want - 1.0) < 3e-4


def test_counts_at_the_published_widths():
    cell = harness.load_cell(CELL)
    cfg, tr = cell["config"], cell["traffic"]
    a = ref.arch(cfg)
    assert a["kinds"].count("attn") == 2 and a["ssm_heads"] == 64
    assert a["d_inner"] == cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    params = jax.eval_shape(lambda k: ref.make_params(cfg, k),
                            jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    assert 1.69e9 < n < 1.71e9
    # the SSD's contractions: 2 chunks of 256 per 512-token row
    l, c, h, p, s = 256, 2, 64, 64, 128
    tri = l * (l + 1) / 2
    assert fam.ssd_flops(a, 2, 512) == 2 * 2 * c * (
        tri * s + tri * h * p + 2 * l * h * p * s)
    flops = fam.validation_flops(cfg, tr)
    assert 2 * 1024 * 1.6e9 < flops < 2 * 1024 * 1.8e9
    # the embedding: one table read and the taken rows (read once,
    # written once per policy)
    from chipbench import costs
    t = fam.fake_quant_tensors(cfg, tr)
    K, V, d = tr["episodes_per_batch"], cfg["vocab_size"], 2048
    assert costs.fake_quant_bytes(t[:2], K) == pytest.approx(
        4 * (V * d + 1024 * d * (1 + K)))


# ---------------------------------------------------------------------------
# The readers of the Mamba-2 scopes
# ---------------------------------------------------------------------------

SCOPES = {
    "loop": "jit(epoch)/while",
    "in": "jit(epoch)/while/body/validation/vmap(mamba)/dot_general",
    "fq": "jit(epoch)/while/body/validation/vmap(mamba)/fake_quant/mul",
    "scan": "jit(epoch)/while/body/validation/vmap(mamba)/ssd/while/body/"
            "dot_general",
    "ssdfq": "jit(epoch)/while/body/validation/mamba/ssd/fake_quant/x",
    "attn": "jit(epoch)/while/body/validation/dot_general",
    "upd": "jit(epoch)/while/body/update/add",
}


def _trace():
    # window 0..100 ns: "loop" is charged where nothing else runs
    evs = [("loop", 0, 100), ("in", 10, 20), ("fq", 20, 25),
           ("scan", 25, 40), ("ssdfq", 40, 42), ("attn", 42, 50),
           ("upd", 60, 70)]
    return T.Trace(window=(0, 100), ops={"/device:TPU:0": evs},
                   host=[(T.WINDOW_SPAN, 0, 100)])


def _ctx(t, **kw):
    base = dict(trace=t, scopes=SCOPES, counters={"traced_batches": 6},
                traffic={"batches_per_epoch": 3})
    base.update(kw)
    return SimpleNamespace(**base)


def test_mamba_and_ssd_readers_split_the_forward():
    ctx = _ctx(_trace())
    ssd = harness.read_metric("stage_ms.ssd", ctx)
    mamba = harness.read_metric("stage_ms.mamba", ctx)
    forward = harness.read_metric("stage_ms.forward", ctx)
    fq = harness.read_metric("stage_ms.fake_quant", ctx)
    # two epochs; fake_quant stays innermost under mamba and ssd
    assert ssd == pytest.approx(15e-9 * 1e3 / 2)
    assert mamba == pytest.approx(10e-9 * 1e3 / 2)
    assert fq == pytest.approx(7e-9 * 1e3 / 2)
    # both are parts of the forward: validation outside fake_quant
    assert forward == pytest.approx((10 + 15 + 8) * 1e-9 * 1e3 / 2)
    assert ssd + mamba < forward
    assert stages.stage_of(SCOPES["scan"], stages.STAGES + ("mamba",
                                                            "ssd")) == "ssd"


def test_mamba_and_ssd_readers_are_silent_without_the_scopes():
    plain = {k: v.replace("vmap(mamba)/", "").replace("mamba/", "")
             .replace("ssd/", "") for k, v in SCOPES.items()}
    ctx = _ctx(_trace(), scopes=plain)
    for name in ("stage_ms.ssd", "stage_ms.mamba"):
        assert harness.read_metric(name, ctx) is None, name
    assert harness.read_metric("stage_ms.forward", ctx) is not None
    empty = SimpleNamespace(trace=T.Trace(window=(0, 100), ops={},
                                          host=[]),
                            scopes={}, counters={}, traffic={})
    for name in ("stage_ms.ssd", "stage_ms.mamba"):
        assert harness.read_metric(name, empty) is None, name
