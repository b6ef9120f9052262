"""The reduction from a profiler trace to busy time, idle gaps and
per-operation seconds."""
from __future__ import annotations

import pytest

from chipbench import trace


def synthetic():
    # window 0..100 ns; device busy 10-30 and 20-40 (overlap), 60-70;
    # one op straddles the window's end
    ops = {"/device:TPU:0": [("a", 10, 30), ("b", 20, 40), ("a", 60, 70),
                             ("c", 95, 120)]}
    host = [(trace.WINDOW_SPAN, 0, 100), ("readback", 38, 62),
            ("epoch", 0, 100)]
    return trace.Trace(window=(0, 100), ops=ops, host=host)


def test_busy_is_the_union_inside_the_window():
    t = synthetic()
    assert t.busy_s() == pytest.approx((30 + 10 + 5) * 1e-9)
    assert t.window_s == pytest.approx(100e-9)


def test_op_seconds_are_self_times_inside_the_window():
    # "a" (10-30) loses the 10 ns that "b" overlaps; "c" is clipped
    got = synthetic().op_seconds()
    assert got == pytest.approx({"a": 20e-9, "b": 20e-9, "c": 5e-9})
    assert sum(got.values()) == pytest.approx(synthetic().busy_s())


def test_idle_gaps_are_named_by_the_host():
    gaps = synthetic().idle_gaps()
    assert [g[0] for g in gaps] == ["epoch", "readback", "epoch"]
    assert [round(g[1] * 1e9) for g in gaps] == [25, 20, 10]


# One steady epoch of the search cell, cut from traces recorded on a
# TPU v5e ("TPU v5 lite") with ``Trace.excerpt``: 3 batches of 4
# episodes, 384 update steps.
RECORDED = {
    "qwen2-0.5b.search-pq": {"window": 0.225574178, "busy": 0.211500851,
                             "fake_quant_roofline": 47.9164527,
                             "mlp3_roofline": 18.7832638,
                             "fq_calls": 1592},
}


@pytest.mark.parametrize("workload", sorted(RECORDED))
def test_recorded_chip_trace_reduces_to_its_metrics(workload):
    import os
    from types import SimpleNamespace

    from chipbench import costs, harness

    want = RECORDED[workload]
    here = os.path.dirname(os.path.abspath(__file__))
    t = trace.from_json(os.path.join(here, "data",
                                     workload + ".epoch.json.gz"))
    assert t.window_s == pytest.approx(want["window"], rel=1e-9)
    assert t.busy_s() == pytest.approx(want["busy"], rel=1e-9)
    # self times of nested ops add up to the busy time
    assert sum(t.op_seconds().values()) == pytest.approx(t.busy_s())
    cell = harness.load_cell(workload)
    ctx = SimpleNamespace(
        trace=t, config=cell["config"], traffic=cell["traffic"],
        costs=costs, family=harness.family(cell["config"]),
        peaks=harness.peaks_for("TPU v5 lite"),
        counters={"traced_batches": 3, "traced_updates": 384,
                  "state_dim": 33, "action_dim": 3,
                  "traced_episodes": 12, "flops_per_episode": 1e9})
    idle = harness.read_metric("idle_share.search", ctx)
    assert idle == pytest.approx(100 * (1 - want["busy"] / want["window"]))
    for name in ("fake_quant_roofline", "mlp3_roofline"):
        got = harness.read_metric(name, ctx)
        assert got == pytest.approx(want[name], rel=1e-6)
        assert 0 < got <= 100
    mlp = t.kernel_calls(lambda r, o: len(o) == 7 and len(r) == 3)
    assert len(mlp) == 5 * 384
    fq = t.kernel_calls(lambda r, o: (len(o) == 1 and len(r) == 2)
                        or (len(o) == 4 and o[0][0] == "s32"))
    assert len(fq) == want["fq_calls"]
    gaps = t.idle_gaps(10)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert sum(g[1] for g in t.idle_gaps(10 ** 6)) == pytest.approx(
        t.window_s - t.busy_s())


def test_a_reader_with_nothing_to_read_is_silent():
    from types import SimpleNamespace

    from chipbench import costs, harness
    t = trace.Trace(window=(0, 100), ops={}, host=[])
    ctx = SimpleNamespace(trace=t, counters={}, config={}, traffic={},
                          costs=costs, peaks={})
    for name in ("idle_share.search", "fake_quant_roofline",
                 "mlp3_roofline", "search_mfu"):
        assert harness.read_metric(name, ctx) is None
