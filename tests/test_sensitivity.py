"""Sensitivity analysis (Eq. 5) tests: fused-vs-sequential parity,
probe legality, dispatch-count bound, and the legality-aware feature
sentinel."""
import copy

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.constraints import legalize, mix_allowed, round_keep
from repro.core.policy import Policy, PolicyBatch, policies_from_batch
from repro.core.sensitivity import (FEATURE_PROBES, MISSING_KL,
                                    SensitivityResult, build_probe_plan,
                                    feature_probe_plan, full_sweep,
                                    kl_divergence, run_sensitivity,
                                    run_sensitivity_sequential)
from repro.core.spec import effective_bits


def test_kl_nonnegative_and_zero_on_self():
    lp = jnp.log(jnp.asarray([[0.7, 0.2, 0.1]]))
    assert float(kl_divergence(lp, lp)) == pytest.approx(0.0, abs=1e-7)
    lq = jnp.log(jnp.asarray([[0.5, 0.3, 0.2]]))
    assert float(kl_divergence(lp, lq)) > 0


def test_run_sensitivity_structure(tiny_lm):
    cm, batch = tiny_lm
    sens = run_sensitivity(cm, batch)
    assert set(sens.table.keys()) == {s.name for s in cm.specs}
    # every quantizable layer has w/a probes, every prunable has p probes
    for s in cm.specs:
        row = sens.table[s.name]
        if s.quantizable:
            assert "w2" in row and "a2" in row
            assert row["w2"] >= 0
        if s.prunable and s.prune_dim:
            assert "p50" in row and "p25" in row


def test_lower_bits_more_sensitive(tiny_lm):
    """On average across layers, 2-bit probes distort more than 4-bit."""
    cm, batch = tiny_lm
    sens = run_sensitivity(cm, batch)
    w2 = [r["w2"] for r in sens.table.values() if "w2" in r]
    w4 = [r["w4"] for r in sens.table.values() if "w4" in r]
    assert np.mean(w2) > np.mean(w4)


def test_more_pruning_more_sensitive(tiny_lm):
    cm, batch = tiny_lm
    sens = run_sensitivity(cm, batch)
    p50 = [r["p50"] for r in sens.table.values() if "p50" in r]
    p25 = [r["p25"] for r in sens.table.values() if "p25" in r]
    assert np.mean(p25) >= np.mean(p50)


def test_features_fixed_length(tiny_lm):
    cm, _ = tiny_lm
    sens = SensitivityResult({s.name: {} for s in cm.specs})
    for s in cm.specs:
        assert len(sens.features_for(s.name)) == 6


# ===========================================================================
# Fused core: parity, dispatch bound, memoization
# ===========================================================================

def _assert_table_parity(fused, seq, tol=1e-6):
    assert set(fused.table) == set(seq.table)
    for name, row in fused.table.items():
        assert set(row) == set(seq.table[name]), name
        for k, v in row.items():
            assert abs(v - seq.table[name][k]) <= tol, \
                (name, k, v, seq.table[name][k])


def test_fused_matches_sequential_lm(tiny_lm):
    """ISSUE 5 acceptance: per layer×probe KL parity <= 1e-6 between
    the one-dispatch fused core and the per-probe host-builder path."""
    cm, batch = tiny_lm
    _assert_table_parity(run_sensitivity(cm, batch, memo=False),
                         run_sensitivity_sequential(cm, batch))


def test_fused_matches_sequential_resnet(tiny_resnet):
    cm, batch = tiny_resnet
    _assert_table_parity(run_sensitivity(cm, batch, memo=False),
                         run_sensitivity_sequential(cm, batch))


@pytest.mark.parametrize("chunk", [1, 3, 8, 1024])
def test_fused_chunking_invariant(tiny_lm, chunk):
    """The scan-chunk size bounds memory, never the numbers (padding
    rows are reference policies and are dropped on the host)."""
    cm, batch = tiny_lm
    base = run_sensitivity(cm, batch, memo=False)
    _assert_table_parity(run_sensitivity(cm, batch, chunk=chunk,
                                         memo=False), base, tol=0.0)


def test_sensitivity_dispatch_count(tiny_lm):
    """One analysis = ONE fused jit execution, zero per-probe
    dispatches (the sensitivity analogue of the epoch dispatch bound)."""
    from _dispatch_probes import assert_sensitivity_dispatch_count
    cm, batch = tiny_lm
    counts = assert_sensitivity_dispatch_count(cm, batch)
    assert counts == {"fused": 1, "seq_probes": 0}


def test_memoized_across_constructors(tiny_lm):
    """Engines built on a common model+batch share one analysis (the
    PopulationSearch construction path)."""
    cm, batch = tiny_lm
    assert run_sensitivity(cm, batch) is run_sensitivity(cm, batch)
    assert run_sensitivity(cm, batch, memo=False) is not \
        run_sensitivity(cm, batch)


def test_full_sweep_is_fused_view(tiny_lm):
    """full_sweep rides the same fused core: rows match a sequential
    per-probe evaluation of the same (legalized) dense plan."""
    from repro.core.sensitivity import _plan_kls_sequential
    cm, batch = tiny_lm
    rows = full_sweep(cm, batch, w_bits=(4, 2), a_bits=(2,), n_prune=3)
    plan = build_probe_plan(cm.specs, w_probes=(4, 2), a_probes=(2,),
                            prune_fracs=tuple(np.linspace(0.1, 1.0, 3)))
    assert len(rows) == len(plan)
    seq = _plan_kls_sequential(cm, batch, plan)
    for r, e, kl in zip(rows, plan.entries, seq):
        assert (r["layer"], r["method"], r["param"]) == \
            (e.layer, e.method, e.param)
        assert abs(r["kl"] - kl) <= 1e-6


# ===========================================================================
# Probe legality (the bugfix satellites)
# ===========================================================================

def _plan_policies(specs, plan):
    return policies_from_batch(specs, PolicyBatch(
        keep=plan.keep, w_bits=plan.w_bits, a_bits=plan.a_bits))


@pytest.mark.parametrize("fixture", ["tiny_lm", "tiny_resnet"])
def test_probes_are_legalize_fixed_points(fixture, request):
    """Every probe row must be a reachable policy: re-applying
    ``legalize`` to any probed CMP changes nothing."""
    cm, _ = request.getfixturevalue(fixture)
    plan = feature_probe_plan(cm.specs)
    for pol, entry in zip(_plan_policies(cm.specs, plan), plan.entries):
        cmp = pol.cmps[entry.spec_idx]
        lc = legalize(cm.specs[entry.spec_idx], copy.deepcopy(cmp))
        assert (lc.keep, effective_bits(lc)) == \
            (cmp.keep, effective_bits(cmp)), (entry, cmp, lc)


def test_prune_probes_respect_granularity(tiny_lm):
    """Probed keep counts are ``round_keep`` outputs — granularity-
    aligned, floored at one granule, capped at the prunable dim (no
    more sub-granule keeps like ``int(prune_dim * frac)`` produced)."""
    cm, _ = tiny_lm
    plan = feature_probe_plan(cm.specs)
    seen = 0
    for p, e in enumerate(plan.entries):
        if e.method != "prune":
            continue
        s = cm.specs[e.spec_idx]
        keep = int(plan.keep[p, e.spec_idx])
        assert keep == round_keep(s, max(1, int(s.prune_dim * e.param)))
        g = max(1, s.prune_granularity)
        assert keep == s.prune_dim or keep % g == 0
        assert keep >= min(g, s.prune_dim)
        seen += 1
    assert seen > 0


def test_quant_probes_int8_fallback(tiny_lm):
    """MIX bit asks on mix_allowed-False layers probe the INT8 fallback
    (the paper's TVM/ARM rule), not an illegal sub-8-bit policy."""
    cm, _ = tiny_lm
    plan = feature_probe_plan(cm.specs)
    checked_fallback = checked_mix = 0
    for p, e in enumerate(plan.entries):
        if e.method not in ("quant_w", "quant_a"):
            continue
        s = cm.specs[e.spec_idx]
        w, a = plan.w_bits[p, e.spec_idx], plan.a_bits[p, e.spec_idx]
        if mix_allowed(s):
            want_w = e.param if e.method == "quant_w" else 32
            want_a = e.param if e.method == "quant_a" else 32
            assert (w, a) == (want_w, want_a), (e, w, a)
            checked_mix += 1
        else:
            assert (w, a) == (8, 8), (e, w, a)
            checked_fallback += 1
    assert checked_fallback > 0 and checked_mix > 0


def test_probe_rows_touch_single_layer(tiny_lm):
    """Each probe differs from the reference policy in exactly the
    probed column (or not at all, when legalization lands back on the
    reference — e.g. a prune probe rounded up to the full dim)."""
    cm, _ = tiny_lm
    plan = feature_probe_plan(cm.specs)
    ref_k, ref_w, ref_a = plan.ref
    for p, e in enumerate(plan.entries):
        for arr, ref in ((plan.keep, ref_k), (plan.w_bits, ref_w),
                         (plan.a_bits, ref_a)):
            diff = np.flatnonzero(arr[p] != ref)
            assert set(diff) <= {e.spec_idx}, (e, diff)


def test_feature_sentinel_distinguishes_unprobed():
    """Missing probes read MISSING_KL, not 0.0 — a non-quantizable
    layer no longer looks maximally robust to the agent."""
    sens = SensitivityResult({"q_only": {"w4": 0.0, "w2": 0.0, "a4": 0.0,
                                         "a2": 0.0},
                              "bare": {}})
    q = sens.features_for("q_only")
    assert q[:4] == [0.0] * 4                 # probed, insensitive
    assert q[4:] == [MISSING_KL] * 2          # not prunable
    assert sens.features_for("bare") == [MISSING_KL] * len(FEATURE_PROBES)
    rows = sens.feature_rows(["q_only", "bare"])
    assert rows.shape == (2, len(FEATURE_PROBES))
    np.testing.assert_array_equal(rows[1], MISSING_KL)


def test_feature_row_feeds_state(tiny_lm):
    """The state builder consumes the array-form feature row (sentinel
    included) for unprobed layers."""
    from repro.core.state import _compute_static_features
    cm, batch = tiny_lm
    sens = run_sensitivity(cm, batch)
    specs = cm.specs
    # head: quantizable but not prunable -> prune features are sentinel
    t = next(i for i, s in enumerate(specs) if s.name == "head")
    static, _, _, _ = _compute_static_features(
        specs, t, sens, _fake_ref_lat(specs))
    assert static[-1] == MISSING_KL and static[-2] == MISSING_KL
    np.testing.assert_allclose(static[-6:], sens.feature_row("head"),
                               rtol=1e-6)


def _fake_ref_lat(specs):
    class U:
        def __init__(self, name):
            self.name, self.time_s = name, 1.0

    class RL:
        units = [U(s.name) for s in specs]
        total_s = float(len(specs))

    return RL()
