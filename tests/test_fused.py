"""The traced rollout: parity with the host-stepped reference.

The contract under test: the search engine runs the whole episode
environment — oracle features, actor with in-scan PRNG exploration,
action->CMP projection, policy carry — as ONE ``jit(lax.scan)``, and
must reproduce the host-stepped rollout (``_search_ref.host_rollout``)
step for step: states, actions, final ``PolicyBatch``, rewards.

Exploration randomness is replayed through the host reference via the
per-batch tail's exposed batch key (the same idiom as
``chunk_sample_keys``), so the comparison exercises every deterministic
stage: the jnp oracle vs the f64 numpy oracle, the static/decided state
features, the vectorized Eq. 4/8 mapping + legalization, and the
reward.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import ArchConfig
from repro.core import latency as latency_mod
from repro.core import state as state_mod
from repro.core.compress import lm_layer_specs
from repro.core.constraints import legal_tables
from repro.core.ddpg import DDPGAgent, DDPGConfig, agent_act_batch
from repro.core.latency import (V5E, HardwareTarget, JaxBatchOracle,
                                LatencyContext, get_batch_oracle,
                                policy_latency_batch)
from repro.core.policy import (Policy, action_columns, map_actions,
                               map_actions_batch, n_actions,
                               policies_from_batch, stack_policies)
from repro.core.reward import RewardConfig
from repro.core.search import (CompressionSearch, PopulationSearch,
                               SearchConfig)
from repro.core.spec import effective_bits

from _search_ref import host_rollout

CFG = ArchConfig(name="o", num_layers=4, d_model=256, num_heads=8,
                 num_kv_heads=4, head_dim=32, d_ff=1024, vocab_size=512)
SPECS = lm_layer_specs(CFG)
CTX = LatencyContext(tokens=1, seq_ctx=512, mode="decode", batch=1)
CTXS = (CTX,
        LatencyContext(tokens=128, seq_ctx=512, mode="prefill", tp=4,
                       chips=4),
        LatencyContext(tokens=4, seq_ctx=0, mode="train"))


def rand_policy(rng) -> Policy:
    return Policy([map_actions(s, rng.random(3), "pq") for s in SPECS])


# ------------------------------------------------------- action mapping

@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_map_actions_batch_matches_scalar(seed):
    """Array mapping == scalar map_actions (+legalize) element for
    element, on every spec, for every method's live fields."""
    rng = np.random.default_rng(seed)
    lt = legal_tables(SPECS)
    for methods in ("p", "q", "pq"):
        ip, iw, ia = action_columns(methods)
        A = rng.random((8, n_actions(methods))).astype(np.float32)
        for i, s in enumerate(SPECS):
            keep, wb, ab = (np.asarray(x) for x in map_actions_batch(
                A, prune_dim=lt.prune_dim[i],
                granularity=lt.granularity[i], prunable=lt.prunable[i],
                quantizable=lt.quantizable[i], mix_ok=lt.mix_ok[i],
                ip=ip, iw=iw, ia=ia))
            for j in range(A.shape[0]):
                cmp = map_actions(s, A[j], methods)
                want_wb, want_ab = effective_bits(cmp)
                if "p" in methods:
                    assert keep[j] == cmp.keep, (methods, s.name, A[j])
                if "q" in methods:
                    assert (wb[j], ab[j]) == (want_wb, want_ab), \
                        (methods, s.name, A[j])


def test_policies_from_batch_roundtrip():
    rng = np.random.default_rng(5)
    pols = [rand_policy(rng) for _ in range(4)]
    back = policies_from_batch(SPECS, stack_policies(SPECS, pols))
    for p, q in zip(pols, back):
        for a, b in zip(p.cmps, q.cmps):
            assert (a.keep, effective_bits(a)) == (b.keep,
                                                   effective_bits(b))
            assert a.mode == b.mode


# -------------------------------------------------------- the jnp oracle

@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_jax_oracle_matches_numpy(seed):
    """JaxBatchOracle == BatchOracle per unit/extra/total (f32 drift
    only), all contexts, plus the in-scan decided_before bookkeeping."""
    rng = np.random.default_rng(seed)
    pols = [rand_policy(rng) for _ in range(5)]
    pb = stack_policies(SPECS, pols)
    for ctx in CTXS:
        want = get_batch_oracle(SPECS, V5E, ctx)(pb)
        jo = JaxBatchOracle(SPECS, V5E, ctx)
        ut, et = jo.unit_times(pb.keep, pb.w_bits, pb.a_bits)
        np.testing.assert_allclose(np.asarray(ut), want.unit_time_s,
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(et), want.extra_time_s,
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(jo.totals(ut, et)),
                                   want.total_s, rtol=1e-5)
        for t in (0, len(SPECS) // 2, len(SPECS)):
            np.testing.assert_allclose(
                np.asarray(jo.decided_before(ut, et, t)),
                want.decided_before(t), rtol=1e-5, atol=1e-12)


def test_jax_oracle_hwp_vmaps_over_targets():
    """One traced oracle serves a stacked HwParams pytree — the
    multi-target rollout's vectorization axis."""
    from repro.core.latency import hw_params
    rng = np.random.default_rng(3)
    pb = stack_policies(SPECS, [rand_policy(rng) for _ in range(3)])
    v5p = HardwareTarget(name="tpu-v5p", peak_bf16=459e12,
                         peak_int8=918e12, hbm_bw=2765e9, ici_bw=90e9)
    jo = JaxBatchOracle(SPECS, V5E, CTX)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), hw_params(V5E),
                           hw_params(v5p))
    totals = jax.vmap(
        lambda hwp: jo.totals(*jo.unit_times(pb.keep, pb.w_bits,
                                             pb.a_bits, hwp), hwp))(stacked)
    for hw, got in zip((V5E, v5p), np.asarray(totals)):
        want = policy_latency_batch(SPECS, pb, hw, CTX).total_s
        np.testing.assert_allclose(got, want, rtol=1e-5)


# --------------------------------------------------------- in-scan actor

def test_agent_act_batch_bounds_and_sigma_zero():
    cfg = DDPGConfig(state_dim=8, action_dim=3)
    agent = DDPGAgent(cfg, seed=0)
    states = np.random.default_rng(0).random((6, 8)).astype(np.float32)
    key = jax.random.PRNGKey(1)
    a = np.asarray(agent_act_batch(
        cfg, agent.state, jnp.asarray(states), key,
        jnp.full(6, 0.5, jnp.float32), jnp.zeros(6, bool)))
    assert a.shape == (6, 3) and np.all((a >= 0) & (a <= 1))
    warm = np.asarray(agent_act_batch(
        cfg, agent.state, jnp.asarray(states), key,
        jnp.full(6, 0.5, jnp.float32), jnp.ones(6, bool)))
    assert np.all((warm >= 0) & (warm < 1))
    # sigma=0 is the deterministic actor — must match the host actor
    det = np.asarray(agent_act_batch(
        cfg, agent.state, jnp.asarray(states), key,
        jnp.zeros(6, jnp.float32), jnp.zeros(6, bool)))
    host = np.stack([agent.act(s, 0.0) for s in states])
    np.testing.assert_allclose(det, host, atol=1e-5)


# ------------------------------------------------------- engine parity

def _mk(tiny_lm, methods, updates=0, batch_size=4, seed=0, sens=None):
    cm, batch = tiny_lm
    ctx = LatencyContext(tokens=1, seq_ctx=256, mode="decode", batch=1)
    scfg = SearchConfig(
        methods=methods, episodes=8, reward=RewardConfig(target_ratio=0.5),
        ddpg=DDPGConfig(warmup_episodes=2, updates_per_episode=updates,
                        batch_size=16, buffer_size=256), seed=seed)
    return CompressionSearch(cm, batch, scfg, ctx, sens=sens,
                             batch_size=batch_size)


@pytest.mark.parametrize("methods", ["p", "q", "pq"])
def test_fused_rollout_matches_batched_engine(tiny_lm, methods):
    """States, actions, final PolicyBatch, and rewards within 1e-5 of
    the host-stepped reference when it replays the traced rollout's
    exact exploration draws — one batch straddling warmup, one fully
    live (norm stats advanced across the boundary)."""
    K = 4
    search = _mk(tiny_lm, methods)
    for first in (0, K):
        args = search._rollout_args(first, K)
        st_snap = args[0]                 # agent state the scan consumed
        out = search._rollout(*args)
        recs_f = search._finish_batch(first, K, out)

        keys = iter(jax.random.split(search._last_batch_key,
                                     len(search.steps)))

        def act_replay(S, sigmas, warm):
            return np.asarray(agent_act_batch(
                search.agent.cfg, st_snap, jnp.asarray(S, jnp.float32),
                next(keys), jnp.asarray(sigmas, jnp.float32),
                jnp.asarray(warm)))

        S_r, A_r, pols_r, accs_r, lats_r, rews_r, sigs_r = host_rollout(
            search, first, K, act_replay)

        S_f, A_f = np.asarray(out[3]), np.asarray(out[4])
        np.testing.assert_allclose(S_f, S_r, atol=1e-5)
        np.testing.assert_allclose(A_f, A_r, atol=1e-5)
        pb_f = stack_policies(search.specs, [r.policy for r in recs_f])
        pb_r = stack_policies(search.specs, pols_r)
        np.testing.assert_array_equal(pb_f.keep, pb_r.keep)
        np.testing.assert_array_equal(pb_f.w_bits, pb_r.w_bits)
        np.testing.assert_array_equal(pb_f.a_bits, pb_r.a_bits)
        for j, a in enumerate(recs_f):
            assert a.reward == pytest.approx(rews_r[j], abs=1e-5)
            assert a.accuracy == pytest.approx(accs_r[j], abs=1e-6)
            assert a.latency_s == pytest.approx(lats_r[j], rel=1e-5)
            assert a.sigma == pytest.approx(sigs_r[j], abs=1e-6)


def test_fused_dispatch_count(tiny_lm):
    """One batch through the per-batch tail = rollout + validation +
    ring write + update chunk: <= 4 jit executions, measured by wrapping
    the compiled entry points themselves — with canaries proving the
    per-step host path never runs."""
    from _dispatch_probes import assert_fused_dispatch_count
    search = _mk(tiny_lm, "pq", updates=2)
    search.run(episodes=8)               # compile + cross warmup
    counts = assert_fused_dispatch_count(search, first_episode=8,
                                         batch_size=4)
    assert counts == {"rollout": 1, "validate": 1, "push": 1,
                      "update": 1, "host_steps": 0}
    assert search.dispatch_log == ["rollout", "validate", "push",
                                   "update"]


# ---------------------------------------------------- fused populations

def test_population_fused_rollouts_match_solo(tiny_lm):
    """fuse_rollouts=True: one vmapped epoch across hardware targets,
    then one vmapped rollout for the trailing partial batch, reproduces
    each member run alone (same seeds -> same PRNG)."""
    v5p = HardwareTarget(name="tpu-v5p", peak_bf16=459e12,
                         peak_int8=918e12, hbm_bw=2765e9, ici_bw=90e9)
    cm, batch = tiny_lm
    ctx = LatencyContext(tokens=1, seq_ctx=256, mode="decode", batch=1)
    scfg = SearchConfig(
        methods="pq", episodes=6, reward=RewardConfig(target_ratio=0.5),
        ddpg=DDPGConfig(warmup_episodes=2, updates_per_episode=2,
                        batch_size=16, buffer_size=256))

    def member(hw, sens=None):
        return CompressionSearch(cm, batch, scfg, ctx, hw=hw, sens=sens,
                                 batch_size=3)

    m0 = member(V5E)
    members = [member(V5E, sens=m0.sens), member(v5p, sens=m0.sens)]
    pop = PopulationSearch(members, fuse_rollouts=True)
    assert pop._rollouts_fusable()
    results = pop.run(episodes=7)            # 2 whole batches + 1
    for m in members:
        assert m.dispatch_log.count("epoch") == 2
        assert m.dispatch_log.count("rollout") == 1
    solos = [member(V5E, sens=m0.sens), member(v5p, sens=m0.sens)]
    for m, res in zip(solos, results):
        want = m.run(episodes=7)
        for a, b in zip(res.history, want.history):
            assert a.reward == pytest.approx(b.reward, abs=1e-6)
            assert a.latency_s == pytest.approx(b.latency_s, rel=1e-6)
            assert a.accuracy == pytest.approx(b.accuracy, abs=1e-6)


def test_population_mixed_methods_falls_back(tiny_lm):
    """Mixed p/q/pq members have different step lists — the population
    keeps per-member (still fused) rollouts and shared updates."""
    cm, batch = tiny_lm
    ctx = LatencyContext(tokens=1, seq_ctx=256, mode="decode", batch=1)

    def member(methods):
        scfg = SearchConfig(
            methods=methods, episodes=4,
            reward=RewardConfig(target_ratio=0.5),
            ddpg=DDPGConfig(warmup_episodes=2, updates_per_episode=2,
                            batch_size=16, buffer_size=256, action_dim=3))
        return CompressionSearch(cm, batch, scfg, ctx, batch_size=2)

    pop = PopulationSearch([member("p"), member("q"), member("pq")],
                           fuse_rollouts=True)
    assert not pop._rollouts_fusable()
    results = pop.run(episodes=4)
    assert len(results) == 3
    for res in results:
        assert [r.episode for r in res.history] == list(range(4))
        assert all(np.isfinite(r.reward) for r in res.history)


# --------------------------------------------------- cache eviction

def test_oracle_cache_evicts_oldest(monkeypatch):
    monkeypatch.setattr(latency_mod, "_ORACLE_CACHE_MAX", 2)
    monkeypatch.setattr(latency_mod, "_oracle_cache", {})
    spec_lists = [lm_layer_specs(CFG) for _ in range(3)]
    oracles = [get_batch_oracle(s, V5E, CTX) for s in spec_lists]
    cache = latency_mod._oracle_cache
    assert len(cache) == 2
    # oldest entry (spec_lists[0]) evicted; newest two retained
    assert get_batch_oracle(spec_lists[1], V5E, CTX) is oracles[1]
    assert get_batch_oracle(spec_lists[2], V5E, CTX) is oracles[2]
    assert all(hit.specs is not spec_lists[0] for hit in cache.values())


def test_static_cache_evicts_oldest(tiny_lm, monkeypatch):
    cm, _ = tiny_lm
    monkeypatch.setattr(state_mod, "_STATIC_CACHE_MAX", 2)
    monkeypatch.setattr(state_mod, "_static_cache", {})
    search = _mk(tiny_lm, "pq")
    from repro.core.state import _static_features
    vals = [_static_features(search.specs, t, search.sens, search.ref_lat)
            for t in search.steps[:3]]
    cache = state_mod._static_cache
    assert len(cache) == 2
    keys = list(cache)
    # the two newest steps survive; re-reading them is a hit (identity)
    assert _static_features(search.specs, search.steps[1], search.sens,
                            search.ref_lat) is vals[1]
    assert list(cache) == keys
