"""The epoch program's stages and host phases as the per-layer readers
see them (``chipbench/stages.py``): device self time by innermost
stage scope, device idle time under the epoch's host phases, and the
slowest epoch of the program's always-on span record."""
from __future__ import annotations

import gzip
import json
import sys
from types import SimpleNamespace

import pytest

from chipbench import harness, stages
from chipbench import trace as T

TPU = "/device:TPU:0"
SCOPES = {
    "loop": "jit(epoch)/while",
    "fq": "jit(epoch)/while/body/validation/vmap()/fake_quant/mul",
    "fwd": "jit(epoch)/while/body/validation/dot_general",
    "upd": "jit(epoch)/while/body/update/while/body/add",
    "act": "jit(epoch)/while/body/rollout/while/body/dot_general",
}
STAGE_METRICS = ("stage_ms.rollout", "stage_ms.fake_quant",
                 "stage_ms.forward", "stage_ms.update", "stage_ms.other")


def synthetic(chips=1):
    # window 0..100 ns; "loop" spans it all and is charged only where
    # no body op runs (25 ns); "bare" has no name stack; "act" is
    # clipped at the window's end
    evs = [("loop", 0, 100), ("fq", 10, 30), ("fwd", 30, 50),
           ("upd", 60, 80), ("bare", 85, 95), ("act", 95, 110)]
    ops = {f"/device:TPU:{c}": list(evs) for c in range(chips)}
    host = [(T.WINDOW_SPAN, 0, 100)]
    return T.Trace(window=(0, 100), ops=ops, host=host)


@pytest.mark.parametrize("chips", [1, 2])
def test_stage_seconds_charge_the_innermost_scope_and_sum_to_busy(chips):
    t = synthetic(chips)
    got = stages.stage_seconds(t, SCOPES)
    want = {"rollout": 5, "validation": 20, "fake_quant": 20, "reward": 0,
            "replay_push": 0, "update": 20, "other": 25 + 10}
    assert got == pytest.approx({k: chips * v * 1e-9
                                 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(t.busy_s() * chips)


@pytest.mark.parametrize("stack,want", [
    ("jit(epoch)/while/body/validation/vmap()/fake_quant/jit(f)/x",
     "fake_quant"),
    ("jit(epoch)/while/body/validation/vmap()/dot_general", "validation"),
    ("jit(epoch)/while/body/replay_push/select_n", "replay_push"),
    ("jit(epoch)/while/body/update_step/add", "other"),
    # a scope under a transform is the scope; a nested jit is not
    ("jit(epoch)/while/body/validation/vmap(fake_quant)/jit(f)/x",
     "fake_quant"),
    ("jit(epoch)/while/transpose(jvp(update))/dot_general", "update"),
    ("jit(epoch)/while/body/jit(rollout)/add", "other"),
    (None, "other"),
])
def test_stage_of_matches_whole_scope_names(stack, want):
    assert stages.stage_of(stack) == want


def _ctx(t, **kw):
    base = dict(trace=t, scopes=SCOPES, counters={"traced_batches": 3},
                traffic={"batches_per_epoch": 3})
    base.update(kw)
    return SimpleNamespace(**base)


def test_the_stage_readers_split_one_epoch():
    ctx = _ctx(synthetic())
    got = {m: harness.read_metric(m, ctx) for m in STAGE_METRICS}
    ns = {"stage_ms.rollout": 5, "stage_ms.fake_quant": 20,
          "stage_ms.forward": 20, "stage_ms.update": 20,
          "stage_ms.other": 35}
    assert got == pytest.approx({k: v * 1e-6 for k, v in ns.items()})
    assert sum(got.values()) == pytest.approx(ctx.trace.busy_s() * 1e3)


def test_epoch_host_ms_is_device_idle_under_the_host_phases():
    t = T.Trace(window=(0, 100),
                ops={TPU: [("a", 11, 58), ("b", 95, 100)]},
                host=[(T.WINDOW_SPAN, 0, 100),
                      ("search.epoch.args", 0, 10),
                      ("search.epoch.dispatch", 10, 12),
                      ("search.epoch.wait", 12, 60),
                      ("search.epoch.readback", 60, 70),
                      ("search.epoch.records", 70, 90)])
    # idle under args 10, dispatch 1, readback 10, records 20; the wait's
    # idle tail (58-60) and the unmarked 90-95 are not the host's phases
    assert stages.host_idle_s(t) == pytest.approx(41e-9)
    ctx = _ctx(t, counters={"traced_batches": 6})
    assert harness.read_metric("epoch_host_ms", ctx) == pytest.approx(
        41e-9 * 1e3 / 2)


def _epoch(first, t0, ms):
    return ("search.epoch", None, t0, t0 + int(ms * 1e6),
            {"first_episode": first})


def test_epoch_stall_ms_reads_the_window_after_the_profiler(capsys):
    # two set-up epochs, then a window of 10: the first (untraced) and
    # three traced ones are left out, whatever they took
    durations = [9000, 9000] + [221, 500, 500, 500, 221, 222, 220, 1500,
                                221, 223]
    rec, t = [("sensitivity", None, 0, 4_000_000_000, {})], 10 ** 10
    for i, ms in enumerate(durations):
        rec.append(_epoch(12 * i, t, ms))
        t += int(ms * 1e6) + 1000
    slow = rec[1 + 9]
    rec.append(("search.epoch.wait", "search.epoch", slow[2], slow[2] + 10,
                {"first_episode": slow[4]["first_episode"]}))
    rec.append(("python.gc", "search.epoch", slow[2] + 20,
                slow[2] + 120_000_000, {"collected": 5}))
    ctx = SimpleNamespace(spans=rec, counters={"window_episodes": 120},
                          traffic={"episodes_per_batch": 4,
                                   "batches_per_epoch": 3,
                                   "trace_epochs": 3})
    got = harness.read_metric("epoch_stall_ms", ctx)
    assert got == pytest.approx(1500 - 221.5)
    line = capsys.readouterr().err
    assert f"first_episode {slow[4]['first_episode']}" in line
    assert "'wait'" in line and "[120.0]" in line
    assert harness.read_metric("sensitivity_s", ctx) == pytest.approx(4.0)


def test_the_new_readers_with_nothing_to_read_are_silent(monkeypatch):
    empty = T.Trace(window=(0, 100), ops={}, host=[])
    ctx = SimpleNamespace(trace=empty, scopes={}, spans=[], counters={},
                          traffic={})
    names = STAGE_METRICS + ("epoch_host_ms", "epoch_stall_ms",
                             "sensitivity_s")
    for name in names:
        assert harness.read_metric(name, ctx) is None, name
    # a program that names no stage and marks no phase (the ops carry
    # name stacks without a listed scope)
    bare = _ctx(synthetic(), scopes={"loop": "jit(epoch)/while"})
    for name in STAGE_METRICS + ("epoch_host_ms",):
        assert harness.read_metric(name, bare) is None, name
    # a program without the span recorder
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    ctx = SimpleNamespace(trace=empty, counters={"window_episodes": 120},
                          traffic={"episodes_per_batch": 4,
                                   "batches_per_epoch": 3})
    for name in ("epoch_stall_ms", "sensitivity_s"):
        assert harness.read_metric(name, ctx) is None, name


def test_an_excerpt_keeps_its_scopes_and_an_old_one_loads(tmp_path):
    t = synthetic()
    raw = stages.excerpt(t, SCOPES, 0, 100)
    assert raw["scopes"] == {k: SCOPES[k] for k in
                             ("act", "fq", "fwd", "loop", "upd")}
    new = tmp_path / "new.json.gz"
    with gzip.open(new, "wt") as f:
        json.dump(raw, f)
    old = tmp_path / "old.json"
    old.write_text(json.dumps(t.excerpt(0, 100)))
    for path, scopes in ((new, raw["scopes"]), (old, {})):
        t2, sc = stages.from_json(str(path))
        assert sc == scopes
        assert t2.window == t.window and t2.busy_s() == t.busy_s()
        # the trace module alone still reads both
        assert T.from_json(str(path)).busy_s() == t.busy_s()


def test_name_stacks_come_from_the_persistent_cache_entry(tmp_path):
    # a compiled program written as JAX's persistent cache writes it;
    # the trace names each op by its instruction's HLO text
    import jax
    import jax.numpy as jnp
    from jax._src import compilation_cache as cc
    from jax._src import xla_bridge

    def f(x):
        with jax.named_scope("rollout"):
            y = jnp.sin(x) * 2.0
        with jax.named_scope("validation"):
            with jax.named_scope("fake_quant"):
                y = jnp.floor(y * 7.0) / 7.0
            return jnp.cos(y).sum()

    compiled = jax.jit(f).lower(jnp.ones(8)).compile()
    backend = xla_bridge.get_backend()
    blob = backend.serialize_executable(compiled.runtime_executable())
    (tmp_path / "jit_f-0a1b-cache").write_bytes(
        cc.compress_executable(cc.combine_executable_and_time(blob, 0)))
    text = compiled.as_text()
    names = stages._INSTRUCTION.findall(text)
    assert names
    ops = [f"%{n} = f32[8]{{0}} op()" for n, _ in names]
    t = T.Trace(window=(0, 100), host=[(T.WINDOW_SPAN, 0, 100)],
                ops={TPU: [(op, i, i + 1) for i, op in enumerate(ops)]})
    got = stages.scopes_from_cache(t, {"jit_f", "jit_g"}, str(tmp_path))
    assert got == {op: stack for op, (_, stack) in zip(ops, names)}
    assert {stages.stage_of(s) for s in got.values()} >= {
        "rollout", "fake_quant", "validation"}
    assert stages.scopes_from_cache(t, {"jit_g"}, str(tmp_path)) == {}


# One steady epoch of the search cell with the name stacks of its ops,
# cut from a trace recorded on a TPU v5e ("TPU v5 lite") with
# ``stages.excerpt`` (the window is the epoch's ``search.run_epoch``
# span; op texts cut to 80 characters except Pallas calls, whose shapes
# the kernel readers parse): 3 batches of 4 episodes.
RECORDED_STAGES = {
    "qwen2-0.5b.search-pq": {
        "window": 0.221386098, "busy": 0.211613251,
        "stage_ms.rollout": 6.428804, "stage_ms.fake_quant": 138.963834,
        "stage_ms.forward": 36.098025, "stage_ms.update": 21.377101,
        "stage_ms.other": 8.745487, "epoch_host_ms": 7.316252,
        "fake_quant_roofline": 47.9115159, "mlp3_roofline": 18.7844207},
}


@pytest.mark.parametrize("workload", sorted(RECORDED_STAGES))
def test_recorded_chip_trace_reduces_to_its_stages(workload):
    import os

    from chipbench import costs

    want = RECORDED_STAGES[workload]
    here = os.path.dirname(os.path.abspath(__file__))
    t, scopes = stages.from_json(os.path.join(here, "data",
                                              workload + ".stages.json.gz"))
    assert t.window_s == pytest.approx(want["window"], rel=1e-9)
    assert t.busy_s() == pytest.approx(want["busy"], rel=1e-9)
    cell = harness.load_cell(workload)
    ctx = SimpleNamespace(
        trace=t, scopes=scopes, config=cell["config"],
        traffic=cell["traffic"], costs=costs,
        family=harness.family(cell["config"]),
        peaks=harness.peaks_for("TPU v5 lite"),
        counters={"traced_batches": 3, "traced_updates": 384,
                  "state_dim": 33, "action_dim": 3})
    got = {m: harness.read_metric(m, ctx) for m in want
           if m not in ("window", "busy")}
    assert got == pytest.approx({m: want[m] for m in got}, rel=1e-6)
    # the five stages are the busy time of the epoch
    assert sum(got[m] for m in STAGE_METRICS) == pytest.approx(
        t.busy_s() * 1e3, rel=1e-9)
    # the host's phases hold most of the idle time
    idle_ms = (t.window_s - t.busy_s()) * 1e3
    assert 0.5 * idle_ms < got["epoch_host_ms"] < idle_ms
    # every op of the epoch program's stages carries its name stack;
    # the kernels are named
    kinds = {T.short_name(n) for n in scopes}
    assert {"%fake_quant_apply", "%fake_quant_range", "%polyak"} <= kinds
    assert {stages.stage_of(s) for s in scopes.values()} >= set(
        stages.STAGES)
