"""Measured-latency subsystem: calibration table, calibrated oracles
(scalar/batch/traced parity + fused dispatch bound), policy deployment
bucketing, and oracle_mode="measured" end to end on the tiny engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.compress import CompressibleLM
from repro.core.latency import (CONTAINERS, LatencyContext, V5E,
                                container_for_bits, get_jax_oracle,
                                policy_latency, policy_latency_batch)
from repro.core.measure import (CalibrationTable, MeasureConfig,
                                deploy_policy_params, fit_calibration,
                                fit_extra_factor, load_calibration,
                                measure_policy, policy_bits_by_name,
                                uniform_policy)
from repro.core.policy import Policy
from repro.core.spec import LayerCMP
from repro.models import model as M

CFG = ArchConfig(name="meas", num_layers=2, d_model=64, num_heads=4,
                 num_kv_heads=2, head_dim=16, d_ff=256, vocab_size=128,
                 scan_layers=True, compute_dtype="float32")
CTX = LatencyContext(tokens=1, seq_ctx=256, mode="decode", batch=1)


@pytest.fixture(scope="module")
def cm():
    return CompressibleLM(CFG, M.init(CFG, jax.random.PRNGKey(0)))


def synth_table(cm):
    return CalibrationTable(
        ratios={s.kind: {"raw": 1.1, "int8": 1.7, "int4": 2.3}
                for s in cm.specs},
        extra={"attn": 1.4, "overhead": 1.4})


def mixed_policy(specs, seed=0):
    rng = np.random.RandomState(seed)
    pol = Policy.reference(specs)
    for s, c in zip(specs, pol.cmps):
        if not s.quantizable:
            continue
        pick = rng.randint(3)
        if pick == 1:
            c.mode, c.w_bits, c.a_bits = "INT8", 8, 8
        elif pick == 2 and s.mix_supported:
            c.mode, c.w_bits, c.a_bits = "MIX", 4, 4
    return pol


# --------------------------- calibration table ------------------------------

def test_table_roundtrip(tmp_path, cm):
    t = synth_table(cm)
    t.meta["note"] = "test"
    p = str(tmp_path / "calib.json")
    t.save(p)
    back = CalibrationTable.load(p)
    assert back.ratios == t.ratios
    assert back.extra_factor() == pytest.approx(1.4)
    assert back.overhead_factor() == pytest.approx(1.4)
    assert back.meta["note"] == "test"


def test_table_load_refuses_other_backend(tmp_path, monkeypatch):
    """A table fitted on one backend is refused on another; the committed
    CPU table stays valid on the CPU."""
    assert load_calibration().meta["backend"] == jax.default_backend()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="fitted on backend 'cpu'"):
        load_calibration()
    t = CalibrationTable(ratios={"mlp_up": {"int8": 2.0}},
                         meta={"backend": "tpu"})
    p = str(tmp_path / "tpu.json")
    t.save(p)
    assert CalibrationTable.load(p).factor("mlp_up", "int8") == 2.0
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    with pytest.raises(ValueError, match="running on 'cpu'"):
        CalibrationTable.load(p)


def test_table_defaults_and_unit_factors(cm):
    t = CalibrationTable(ratios={"mlp_up": {"int8": 2.0}})
    assert t.factor("mlp_up", "int8") == 2.0
    assert t.factor("mlp_up", "raw") == 1.0       # missing container -> 1
    assert t.factor("nope", "int8") == 1.0        # missing kind -> 1
    assert t.extra_factor() == 1.0
    f = t.unit_factors(cm.specs)
    assert f.shape == (len(cm.specs), len(CONTAINERS))
    i8 = CONTAINERS.index("int8")
    for i, s in enumerate(cm.specs):
        want = 2.0 if s.kind == "mlp_up" else 1.0
        assert f[i, i8] == want


def test_fit_calibration_geomean():
    rows = [{"kind": "mlp_up", "container": "int8", "ratio": 2.0},
            {"kind": "mlp_up", "container": "int8", "ratio": 8.0},
            {"kind": "mlp_up", "container": "raw", "ratio": 1.5},
            {"kind": "head", "container": "int8", "ratio": float("inf")},
            {"kind": "head", "container": "int8", "ratio": -1.0},
            {"kind": "embed", "skipped": "whatever"}]
    t = fit_calibration(rows)
    assert t.factor("mlp_up", "int8") == pytest.approx(4.0)   # geomean
    assert t.factor("mlp_up", "raw") == pytest.approx(1.5)
    assert t.factor("head", "int8") == 1.0        # junk filtered out
    assert "embed" not in t.ratios


def test_fit_extra_factor_exact_on_ref(cm):
    """By construction the fitted residual makes the calibrated raw
    prediction reproduce the whole-model measurement exactly."""
    t = synth_table(cm)
    ref = Policy.reference(cm.specs)
    target = 2.5 * policy_latency(cm.specs, ref, V5E, CTX, calib=t).total_s
    fit_extra_factor(t, cm.specs, ref, target, V5E, CTX)
    got = policy_latency(cm.specs, ref, V5E, CTX, calib=t).total_s
    assert got == pytest.approx(target, rel=1e-9)


# --------------------------- calibrated oracles -----------------------------

def test_three_oracle_calibrated_parity(cm):
    """Scalar, numpy-batch and traced oracles agree under a calibration
    table, and all differ from the analytic numbers (factors applied)."""
    t = synth_table(cm)
    pols = [mixed_policy(cm.specs, s) for s in range(4)]
    scalar = np.array([policy_latency(cm.specs, p, V5E, CTX,
                                      calib=t).total_s for p in pols])
    batch = policy_latency_batch(cm.specs, pols, V5E, CTX, calib=t)
    np.testing.assert_allclose(batch.total_s, scalar, rtol=1e-12)
    jo = get_jax_oracle(cm.specs, V5E, CTX, calib=t)
    from repro.core.policy import stack_policies
    pb = stack_policies(cm.specs, pols)
    ut, et = jo.unit_times(pb.keep, pb.w_bits, pb.a_bits)
    traced = np.asarray(jo.totals(ut, et))
    np.testing.assert_allclose(traced, scalar, rtol=1e-4)
    analytic = np.array([policy_latency(cm.specs, p, V5E, CTX).total_s
                         for p in pols])
    assert np.all(scalar > analytic)    # factors > 1 everywhere


def test_oracle_cache_keyed_on_calib(cm):
    t1, t2 = synth_table(cm), synth_table(cm)
    a = get_jax_oracle(cm.specs, V5E, CTX, calib=t1)
    assert get_jax_oracle(cm.specs, V5E, CTX, calib=t1) is a
    assert get_jax_oracle(cm.specs, V5E, CTX, calib=t2) is not a
    assert get_jax_oracle(cm.specs, V5E, CTX) is not a


def test_calibrated_fused_dispatch_bound():
    """oracle_mode="calibrated" keeps the per-batch tail at the analytic
    oracle's <=4-dispatch bound."""
    from _dispatch_probes import calibrated_fused_row
    row = calibrated_fused_row(batch_size=4, updates=4)
    assert row["dispatches_per_batch"] <= 4


def test_bad_oracle_mode_rejected(cm):
    from repro.core.search import CompressionSearch, SearchConfig
    with pytest.raises(ValueError, match="oracle_mode"):
        CompressionSearch(cm, {"tokens": jnp.zeros((1, 8), jnp.int32)},
                          SearchConfig(oracle_mode="wallclock"), CTX)


# --------------------------- deployment bucketing ---------------------------

def test_policy_bits_widest_wins(cm):
    """Scan-stacked arrays deploy at the widest width any layer asks
    for: one FP32 layer keeps the shared weight raw even when the other
    layer asks int8."""
    pol = uniform_policy(cm.specs, "int8")
    idx = [i for i, s in enumerate(cm.specs) if s.kind == "mlp_up"]
    pol.cmps[idx[0]] = LayerCMP(keep=cm.specs[idx[0]].prune_dim,
                                mode="FP32")
    bits = policy_bits_by_name(cm.specs, pol)
    assert bits["w_up"] == 32                  # widest (raw) wins
    assert bits["w_down"] == 8
    qp = deploy_policy_params(cm, pol)
    assert "w" in qp["blocks"]["mlp"]["w_up"]          # stayed raw
    assert "w_q" in qp["blocks"]["mlp"]["w_down"]      # int8 container


def test_deployed_policy_forward_runs(cm):
    """A mixed search policy deploys onto real integer containers and
    the deployed forward stays close to the reference model."""
    pol = uniform_policy(cm.specs, "int4")
    qp = deploy_policy_params(cm, pol)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, 128)
    base = M.forward(CFG, cm.params, tokens=toks)
    out = M.forward(CFG, qp, tokens=toks)
    rel = float(jnp.linalg.norm(out - base) / jnp.linalg.norm(base))
    assert rel < 0.6


def test_measure_policy_memo(cm, monkeypatch):
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(4), (2, 16),
                                          0, 128)}
    mcfg = MeasureConfig(warmup=1, repeats=1)
    pol = uniform_policy(cm.specs, "int8")
    t1 = measure_policy(cm, pol, batch, mcfg)
    assert t1 > 0
    # identical container signature -> memo hit, no re-deploy
    import repro.core.measure as measure_mod
    monkeypatch.setattr(
        measure_mod, "quantize_params_for_deploy",
        lambda *a, **k: pytest.fail("memo miss re-deployed params"))
    assert measure_policy(cm, pol, batch, mcfg) == t1


# --------------------------- measured search mode ---------------------------

@pytest.mark.slow
def test_measured_mode_times_top_k(cm):
    from repro.core.reward import RewardConfig
    from repro.core.ddpg import DDPGConfig
    from repro.core.search import CompressionSearch, SearchConfig
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(5), (2, 16),
                                          0, 128)}
    scfg = SearchConfig(
        methods="q", episodes=6, reward=RewardConfig(target_ratio=0.6),
        ddpg=DDPGConfig(warmup_episodes=2, updates_per_episode=2,
                        batch_size=8, buffer_size=64),
        oracle_mode="measured", measure_top_k=2, seed=0)
    cm2 = CompressibleLM(CFG, cm.params)
    search = CompressionSearch(cm2, batch, scfg, CTX,
                               calib=synth_table(cm))
    res = search.run()
    assert res.measured is not None and len(res.measured) == 2
    for row in res.measured:
        assert row["measured_s"] > 0 and row["measured_ref_s"] > 0
        assert row["measured_ratio"] == pytest.approx(
            row["measured_s"] / row["measured_ref_s"])
        assert row["predicted_ratio"] > 0
    # sorted by reward, best first
    assert res.measured[0]["reward"] >= res.measured[1]["reward"]


def test_container_for_bits_buckets():
    assert container_for_bits(32) == "raw"
    assert container_for_bits(9) == "raw"
    assert container_for_bits(8) == "int8"
    assert container_for_bits(5) == "int8"
    assert container_for_bits(4) == "int4"
    assert container_for_bits(2) == "int4"
