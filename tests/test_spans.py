"""The program's own host spans (``repro.core.spans``) and the named
scopes of the epoch program: what the benchmark's per-stage and
per-phase readers rely on."""
import gc
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import spans
from repro.core.ddpg import DDPGConfig
from repro.core.latency import LatencyContext
from repro.core.reward import RewardConfig
from repro.core.search import (CompressionSearch, PopulationSearch,
                               SearchConfig)

EPOCH_PHASES = ("search.epoch", "search.epoch.args",
                "search.epoch.dispatch", "search.epoch.wait",
                "search.epoch.readback", "search.epoch.records")
STAGE_SCOPES = ("rollout", "validation", "fake_quant", "reward",
                "replay_push", "update")


@pytest.fixture
def record():
    spans.drain()
    yield
    spans.drain()


def test_a_span_records_its_parent_times_and_attributes(record):
    with spans.span("outer", k=3):
        with spans.span("inner"):
            pass
    inner, outer = spans.drain()
    assert inner[:2] == ("inner", "outer") and inner[4] == {}
    assert outer[:2] == ("outer", None) and outer[4] == {"k": 3}
    assert outer[2] <= inner[2] <= inner[3] <= outer[3]
    assert spans.drain() == []


def test_the_record_is_bounded_and_keeps_the_newest(record):
    for i in range(spans.MAXLEN + 10):
        with spans.span("s", i=i):
            pass
    got = spans.drain()
    assert len(got) == spans.MAXLEN
    assert got[0][4]["i"] == 10 and got[-1][4]["i"] == spans.MAXLEN + 9


def test_a_span_closes_when_its_body_raises(record):
    with pytest.raises(ValueError):
        with spans.span("fails"):
            raise ValueError
    with spans.span("after"):
        pass
    assert [(s[0], s[1]) for s in spans.drain()] == [("fails", None),
                                                     ("after", None)]


def test_a_generation_2_collection_is_a_gc_span(record):
    spans.watch_gc()
    spans.watch_gc()            # idempotent
    assert gc.callbacks.count(spans._on_gc) == 1
    with spans.span("holder"):
        gc.collect(2)
    got = spans.drain()
    gcs = [s for s in got if s[0] == spans.GC_SPAN]
    assert len(gcs) == 1
    assert gcs[0][1] == "holder" and "collected" in gcs[0][4]
    assert got[-1][0] == "holder"
    gc.collect(0)               # younger generations are not recorded
    assert not [s for s in spans.drain() if s[0] == spans.GC_SPAN]


def _engine(tiny_lm, seed=0, sens=None):
    cm, batch = tiny_lm
    ctx = LatencyContext(tokens=1, seq_ctx=256, mode="decode", batch=1)
    scfg = SearchConfig(
        methods="pq", episodes=8, reward=RewardConfig(target_ratio=0.5),
        ddpg=DDPGConfig(warmup_episodes=2, updates_per_episode=2,
                        batch_size=16, buffer_size=256), seed=seed)
    return CompressionSearch(cm, batch, scfg, ctx, sens=sens,
                             batch_size=2, epoch_batches=2)


def _epoch_spans(got, first):
    """The ``search.epoch*`` spans of the epoch that starts at episode
    ``first``, in the order they started."""
    mine = [s for s in got if s[0].startswith("search.epoch")
            and s[4].get("first_episode") == first]
    return sorted(mine, key=lambda s: s[2])


def _assert_phases(mine, members=1):
    names = [s[0] for s in mine]
    assert names == (list(EPOCH_PHASES[:3])
                     + list(EPOCH_PHASES[3:]) * members)
    epoch = mine[0]
    assert epoch[1] is None
    assert all(s[1] == "search.epoch" for s in mine[1:])
    assert all(epoch[2] <= s[2] <= s[3] <= epoch[3] for s in mine[1:])
    # the phases follow one another
    assert all(a[3] <= b[2] for a, b in zip(mine[1:], mine[2:]))


def test_a_fused_epoch_leaves_its_six_phase_spans(tiny_lm, record):
    eng = _engine(tiny_lm)
    eng.run_epoch(0, 2)
    eng.run_epoch(4, 2)
    got = spans.drain()
    for first in (0, 4):
        _assert_phases(_epoch_spans(got, first))


def test_a_population_epoch_leaves_its_phase_spans(tiny_lm, record):
    m0 = _engine(tiny_lm)
    pop = PopulationSearch([m0, _engine(tiny_lm, seed=1, sens=m0.sens)],
                           fuse_rollouts=True)
    assert pop._epochs_fusable()
    spans.drain()
    pop.run_epoch(0, 2)
    # one shared args and dispatch; each member waits, reads back and
    # builds its records
    _assert_phases(_epoch_spans(spans.drain(), 0), members=2)


def test_the_sensitivity_analysis_is_a_span(tiny_lm, record):
    from repro.core.sensitivity import run_sensitivity
    cm, batch = tiny_lm
    run_sensitivity(cm, batch, memo=False)
    got = [s for s in spans.drain() if s[0] == "sensitivity"]
    assert len(got) == 1 and got[0][3] > got[0][2]


def test_the_epoch_program_carries_every_stage_scope(tiny_lm, monkeypatch):
    # the kernel route puts fake quantization in a Pallas call; its
    # scope must tag that call too
    monkeypatch.setenv("GALEN_FQ_KERNEL", "1")
    eng = _engine(tiny_lm)
    schedule = (4, 4)
    fn = eng._make_epoch_fn(schedule)
    args = (eng.cmodel.params,) + eng._epoch_args(0, 2)
    # the compiled HLO's op metadata: the name stacks a profile shows
    text = jax.jit(fn).lower(*args).compile().as_text()
    stacks = [n.split("/") for n in re.findall(r'op_name="([^"]*)"', text)]
    for scope in STAGE_SCOPES:
        assert any(scope in st for st in stacks), scope
    # both kernel passes, by their names, inside validation's fake
    # quantization
    for kernel in ("fake_quant_range", "fake_quant_apply"):
        assert any(kernel in st and "fake_quant" in st
                   and "validation" in st for st in stacks), kernel


def test_fake_quant_is_scoped_and_unchanged():
    from repro.core.quantization import dequantize, fake_quant, quantize
    x = jnp.linspace(-1.0, 1.0, 64, dtype=jnp.float32).reshape(8, 8)
    text = jax.jit(lambda v: fake_quant(v, 4)).lower(x).compile().as_text()
    assert "/fake_quant/" in text
    # the scope changes no value: the reference quantize-dequantize
    want = dequantize(*quantize(x, 4, axis=(0,)))
    assert jnp.array_equal(fake_quant(x, 4), want)
