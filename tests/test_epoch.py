"""The epoch program: parity with the per-batch tail.

The contract under test: ``run_epoch`` runs E whole episode batches —
rollout, traced-cspec validation, reward, ``DeviceReplay`` ring write,
and the update chunk — as ONE ``jit(lax.scan)`` with donated buffers
and a single host readback, and must reproduce the engine's per-batch
tail run batch by batch (``_run_batches``) exactly: episode records,
the final ``AgentState``, and the replay ring contents.

No noise replay harness is needed: the epoch scan carries the SAME PRNG
streams (the rollout key and the agent's update-sampling key) and
consumes them with the same split pattern as the per-batch tail, so two
same-seed engines draw identically by construction. The comparison therefore exercises every
stage — exploration, the in-scan normalizer advance, validation,
reward, the ring write order, and the masked in-scan update chunks
(including warmup-straddling batches, whose static update schedules
differ from the steady state).
"""
import jax
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.ddpg import DDPGConfig
from repro.core.latency import HardwareTarget, LatencyContext, V5E
from repro.core.replay import (DeviceReplay, ReplayBuffer,
                               device_replay_push)
from repro.core.reward import RewardConfig
from repro.core.search import (CompressionSearch, PopulationSearch,
                               SearchConfig)


_testbed_cache = {}


def _testbed():
    """Module-cached twin of the ``tiny_lm`` fixture for the
    ``@given`` property tests (hypothesis cannot mix strategy
    parameters with function-scoped pytest fixtures)."""
    if "lm" not in _testbed_cache:
        from repro.configs.base import ArchConfig
        from repro.core.compress import CompressibleLM
        from repro.data.pipeline import bigram_lm
        from repro.models import model as M

        cfg = ArchConfig(name="t-epoch", num_layers=3, d_model=64,
                         num_heads=4, num_kv_heads=2, head_dim=16,
                         d_ff=256, vocab_size=128, scan_layers=True)
        params = M.init(cfg, jax.random.PRNGKey(0))
        batch = bigram_lm(cfg.vocab_size, 8, 32, seed=3)
        _testbed_cache["lm"] = (CompressibleLM(cfg, params), batch)
    return _testbed_cache["lm"]


def _mk(tiny_lm, methods, updates=2, batch_size=4, epoch_batches=1,
        seed=0, sens=None, episodes=16, hw=V5E):
    cm, batch = tiny_lm
    ctx = LatencyContext(tokens=1, seq_ctx=256, mode="decode", batch=1)
    scfg = SearchConfig(
        methods=methods, episodes=episodes,
        reward=RewardConfig(target_ratio=0.5),
        ddpg=DDPGConfig(warmup_episodes=2, updates_per_episode=updates,
                        batch_size=16, buffer_size=256), seed=seed)
    return CompressionSearch(cm, batch, scfg, ctx, hw=hw, sens=sens,
                             batch_size=batch_size,
                             epoch_batches=epoch_batches)


def _run_batches(search, episodes):
    """The per-batch tail run batch by batch: the epoch's reference."""
    K = search.batch_size
    recs = []
    for e in range(0, episodes, K):
        recs += search.run_episode_batch(e, min(K, episodes - e))
    return recs


def _assert_records_match(recs_a, recs_b):
    assert [r.episode for r in recs_a] == [r.episode for r in recs_b]
    for a, b in zip(recs_a, recs_b):
        assert a.reward == pytest.approx(b.reward, abs=1e-5)
        assert a.accuracy == pytest.approx(b.accuracy, abs=1e-6)
        assert a.latency_s == pytest.approx(b.latency_s, rel=1e-5)
        assert a.sigma == pytest.approx(b.sigma, abs=1e-6)
        for ca, cb in zip(a.policy.cmps, b.policy.cmps):
            assert (ca.keep, ca.mode, ca.w_bits, ca.a_bits) == \
                (cb.keep, cb.mode, cb.w_bits, cb.a_bits)


def _assert_trees_close(ta, tb, tol=2e-5):
    for la, lb in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
        np.testing.assert_allclose(np.asarray(la, np.float64),
                                   np.asarray(lb, np.float64),
                                   atol=tol, rtol=tol)


# ------------------------------------------------------- engine parity

@pytest.mark.parametrize("methods", [
    "pq",                                     # the joint agent: tier-1
    pytest.param("p", marks=pytest.mark.slow),
    pytest.param("q", marks=pytest.mark.slow),
])
def test_epoch_matches_per_batch_engine(tiny_lm, methods):
    """run() through epochs of 2 batches == the per-batch tail: records
    (reward/accuracy/latency/sigma/policies) and ring contents within
    1e-5. The first epoch straddles the agent's warmup boundary, so
    both the partial-budget and the steady update schedules are
    exercised. The final AgentState is compared at 1e-3: the engines'
    weights match op-for-op, but the running-norm stats accumulate in
    f32 on device vs f64-counted numpy on host (~1e-7 relative), and
    ~30 update GEMMs amplify that — the strict 1e-5 state bound is
    asserted update-free in ``test_epoch_state_parity_no_updates``."""
    epoch = _mk(tiny_lm, methods, epoch_batches=2)
    ref = _mk(tiny_lm, methods, sens=epoch.sens)
    res_e = epoch.run(episodes=16)
    recs_r = _run_batches(ref, 16)
    assert epoch.dispatch_log == ["epoch", "epoch"]
    _assert_records_match(res_e.history, recs_r)
    assert res_e.best.episode == max(recs_r, key=lambda r: r.reward).episode
    # final agent state (actor/critic/targets/Adam/norm/reward-MA/key)
    _assert_trees_close(epoch.agent.state_for_dispatch(),
                        ref.agent.state_for_dispatch(), tol=1e-3)
    # ring contents and host mirrors (rollout-side values: strict)
    assert (epoch.replay.ptr, epoch.replay.size) == \
        (ref.replay.ptr, ref.replay.size)
    _assert_trees_close(epoch.replay.data, ref.replay.data, tol=1e-5)
    # rollout PRNG stream position stayed in lockstep
    np.testing.assert_array_equal(np.asarray(epoch._rollout_key),
                                  np.asarray(ref._rollout_key))


def test_epoch_state_parity_no_updates(tiny_lm):
    """With the update amplifier off, the full final AgentState —
    norm stats included — matches the per-batch tail within 1e-5."""
    epoch = _mk(tiny_lm, "pq", updates=0, epoch_batches=2)
    ref = _mk(tiny_lm, "pq", updates=0, sens=epoch.sens)
    res_e = epoch.run(episodes=16)
    _assert_records_match(res_e.history, _run_batches(ref, 16))
    _assert_trees_close(epoch.agent.state_for_dispatch(),
                        ref.agent.state_for_dispatch(), tol=1e-5)


@pytest.mark.slow
@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=2, deadline=None)
def test_epoch_parity_random_seeds(seed):
    """Property form of the parity contract over agent seeds (new actor
    init, new exploration stream, new replay sampling each time)."""
    s = seed % 1000
    tb = _testbed()
    epoch = _mk(tb, "pq", epoch_batches=3, seed=s, batch_size=3)
    ref = _mk(tb, "pq", sens=epoch.sens, seed=s, batch_size=3)
    recs_e = epoch.run(episodes=9).history
    recs_r = _run_batches(ref, 9)
    _assert_records_match(recs_e, recs_r)
    _assert_trees_close(epoch.agent.state_for_dispatch(),
                        ref.agent.state_for_dispatch(), tol=1e-3)


def test_epoch_best_tracking_matches_history(tiny_lm):
    """The in-carry argmax equals the host-side best over the epoch's
    records (strict >, earliest max wins)."""
    epoch = _mk(tiny_lm, "pq", epoch_batches=4)
    recs = epoch.run_epoch(0, 4)
    best_ep, best_r = epoch.last_epoch_best
    want = max(recs, key=lambda r: r.reward)
    assert best_r == pytest.approx(want.reward, abs=1e-6)
    assert best_ep == want.episode


def test_epoch_remainder_falls_back_to_batches(tiny_lm):
    """Episode counts that don't fill a batch run the remainder through
    the per-batch tail — same numbering, same records."""
    epoch = _mk(tiny_lm, "pq", epoch_batches=2)
    ref = _mk(tiny_lm, "pq", sens=epoch.sens)
    res_e = epoch.run(episodes=14)  # epochs of 8 and 4, then 2 remainder
    assert [r.episode for r in res_e.history] == list(range(14))
    _assert_records_match(res_e.history, _run_batches(ref, 14))
    assert "rollout" in epoch.dispatch_log   # the per-batch tail ran
    assert "epoch" in epoch.dispatch_log


def test_epoch_schedule_is_static_and_cached(tiny_lm):
    """Warmup-straddling and steady epochs compile separate executables
    (static update schedules); re-running reuses them."""
    epoch = _mk(tiny_lm, "pq", epoch_batches=2)
    assert epoch._update_schedule(0, 2) != epoch._update_schedule(8, 2)
    epoch.run(episodes=16)
    n = len(epoch._epoch_cache)
    epoch.run(episodes=16)
    assert len(epoch._epoch_cache) == n   # no new compilations


def test_epoch_dispatch_count(tiny_lm):
    """One post-compile epoch = ONE jit execution (the ISSUE 4
    acceptance bound), measured by wrapping the compiled epoch
    executables — with canaries proving the per-batch entry points
    (rollout/validate/push/update jits) and the host path never ran."""
    from _dispatch_probes import assert_epoch_dispatch_count
    epoch = _mk(tiny_lm, "pq", epoch_batches=2)
    epoch.run(episodes=16)               # compile both schedules
    counts = assert_epoch_dispatch_count(epoch, first_episode=8,
                                         n_batches=2)
    assert counts == {"epoch": 1, "rollout": 0, "validate": 0,
                      "push": 0, "update": 0, "host_steps": 0}


# ---------------------------------------------------- epoch populations

@pytest.mark.slow
def test_population_epoch_matches_solo(tiny_lm):
    """One vmapped epoch dispatch across hardware targets reproduces
    each member run alone (same seeds -> same PRNG streams)."""
    v5p = HardwareTarget(name="tpu-v5p", peak_bf16=459e12,
                         peak_int8=918e12, hbm_bw=2765e9, ici_bw=90e9)

    def member(hw, sens=None):
        return _mk(tiny_lm, "pq", batch_size=3, epoch_batches=2,
                   sens=sens, hw=hw)

    m0 = member(V5E)
    pop = PopulationSearch([member(V5E, sens=m0.sens),
                            member(v5p, sens=m0.sens)],
                           fuse_rollouts=True)
    assert pop._epochs_fusable()
    results = pop.run(episodes=12)
    for m in pop.members:
        assert m.dispatch_log == ["epoch", "epoch"]
    solos = [member(V5E, sens=m0.sens), member(v5p, sens=m0.sens)]
    for m, res in zip(solos, results):
        want = m.run(episodes=12)
        _assert_records_match(res.history, want.history)


def test_population_epoch_requires_shared_reward(tiny_lm):
    """Members whose epoch traces can't be shared (here: different
    reward configs, which bake into the trace) fall back to per-member
    epoch dispatches — same batch decomposition, still one execution
    per member per epoch."""
    cm, batch = tiny_lm
    ctx = LatencyContext(tokens=1, seq_ctx=256, mode="decode", batch=1)

    def member(c, sens=None):
        scfg = SearchConfig(
            methods="pq", episodes=4, reward=RewardConfig(target_ratio=c),
            ddpg=DDPGConfig(warmup_episodes=2, updates_per_episode=2,
                            batch_size=16, buffer_size=256))
        return CompressionSearch(cm, batch, scfg, ctx, sens=sens,
                                 batch_size=2, epoch_batches=2)

    m0 = member(0.5)
    pop = PopulationSearch([m0, member(0.6, sens=m0.sens)],
                           fuse_rollouts=True)
    assert pop._rollouts_fusable() and not pop._epochs_fusable()
    results = pop.run(episodes=4)
    for m, res in zip(pop.members, results):
        assert m.dispatch_log == ["epoch"]
        assert [r.episode for r in res.history] == list(range(4))
        assert all(np.isfinite(rec.reward) for rec in res.history)


# ------------------------------------------------- pure ring push

@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_device_replay_push_matches_host_reference(seed):
    """The pure scan-safe ring write == the host ReplayBuffer reference
    across wraps and oversized batches."""
    rng = np.random.default_rng(seed)
    cap, sd, ad = 16, 3, 2
    host = ReplayBuffer(cap, sd, ad)
    dev = DeviceReplay(cap, sd, ad)
    data = dev.data
    ptr = size = 0
    for _ in range(4):
        n = int(rng.integers(1, 2 * cap))
        s = rng.random((n, sd)).astype(np.float32)
        a = rng.random((n, ad)).astype(np.float32)
        r = rng.random(n).astype(np.float32)
        s2 = rng.random((n, sd)).astype(np.float32)
        d = (rng.random(n) < 0.1).astype(np.float32)
        host.push_batch(s, a, r, s2, d)
        data = device_replay_push(data, s, a, r, s2, d)
        ptr, size = (ptr + n) % cap, min(size + n, cap)
    assert (int(data.ptr), int(data.size)) == (host.ptr, host.size)
    assert (ptr, size) == (host.ptr, host.size)
    np.testing.assert_allclose(np.asarray(data.states), host.states)
    np.testing.assert_allclose(np.asarray(data.actions), host.actions)
    np.testing.assert_allclose(np.asarray(data.rewards), host.rewards)
    np.testing.assert_allclose(np.asarray(data.next_states),
                               host.next_states)
    np.testing.assert_allclose(np.asarray(data.dones), host.dones)
