"""Batched host pieces: parity with the scalar path + properties.

Covers the vectorized oracle, accuracy and state builder against their
scalar forms, the host replay buffer, the engine's per-episode
schedule and legality, and ``PopulationSearch``.
"""
import copy

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import ArchConfig
from repro.core.compress import lm_layer_specs
from repro.core.ddpg import DDPGConfig
from repro.core.latency import (V5E, LatencyContext, policy_latency,
                                policy_latency_batch)
from repro.core.policy import Policy, map_actions, stack_policies
from repro.core.replay import ReplayBuffer
from repro.core.reward import RewardConfig
from repro.core.search import (CompressionSearch, PopulationSearch,
                               SearchConfig)
from repro.core.state import build_state, build_state_batch

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


# ---------------------------------------------------------------- oracle

@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_latency_batch_matches_scalar(seed):
    """policy_latency_batch == scalar policy_latency, all contexts."""
    rng = np.random.default_rng(seed)
    pols = [rand_policy(rng) for _ in range(6)]
    for ctx in CTXS:
        batched = policy_latency_batch(SPECS, pols, V5E, ctx).total_s
        scalar = np.asarray(
            [policy_latency(SPECS, p, V5E, ctx).total_s for p in pols])
        np.testing.assert_allclose(batched, scalar, rtol=1e-6, atol=1e-12)


def test_latency_batch_matches_scalar_resnet(tiny_resnet):
    cm, _ = tiny_resnet
    rng = np.random.default_rng(3)
    img_ctx = LatencyContext(tokens=1, seq_ctx=0, mode="prefill", batch=1)
    pols = [Policy([map_actions(s, rng.random(3), "pq") for s in cm.specs])
            for _ in range(5)]
    batched = policy_latency_batch(cm.specs, pols, V5E, img_ctx).total_s
    scalar = np.asarray(
        [policy_latency(cm.specs, p, V5E, img_ctx).total_s for p in pols])
    np.testing.assert_allclose(batched, scalar, rtol=1e-6, atol=1e-12)


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_oracle_monotone_in_bits(seed):
    """Lowering effective bits (FP32 -> INT8 -> MIX4) never increases
    modeled latency."""
    rng = np.random.default_rng(seed)
    base = rand_policy(rng)
    ladder = (("FP32", 32, 32), ("INT8", 8, 8), ("MIX", 4, 4))
    prev = None
    for mode, wb, ab in ladder:
        pol = copy.deepcopy(base)
        for s, c in zip(SPECS, pol.cmps):
            if s.quantizable and (mode != "MIX" or s.mix_supported):
                c.mode, c.w_bits, c.a_bits = mode, wb, ab
        lat = policy_latency_batch(SPECS, [pol], V5E, CTX).total_s[0]
        if prev is not None:
            assert lat <= prev * (1 + 1e-12)
        prev = lat


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_oracle_monotone_in_keep(seed):
    """Lowering any unit's keep fraction never increases latency."""
    rng = np.random.default_rng(seed)
    pol = rand_policy(rng)
    lat0 = policy_latency_batch(SPECS, [pol], V5E, CTX).total_s[0]
    prunable = [i for i, s in enumerate(SPECS)
                if s.prunable and s.prune_dim]
    i = prunable[int(rng.integers(0, len(prunable)))]
    lower = copy.deepcopy(pol)
    lower.cmps[i].keep = max(1, lower.cmps[i].keep
                             - int(rng.integers(1, lower.cmps[i].keep + 1)))
    lat1 = policy_latency_batch(SPECS, [lower], V5E, CTX).total_s[0]
    assert lat1 <= lat0 * (1 + 1e-12)


def test_oracle_reference_matches_scalar_object():
    ref = Policy.reference(SPECS)
    b = policy_latency_batch(SPECS, [ref], V5E, CTX)
    s = policy_latency(SPECS, ref, V5E, CTX)
    assert b.total_s[0] == pytest.approx(s.total_s, rel=1e-9)
    assert b.unit_time_s.shape == (1, len(SPECS))
    # decided_before(L) + overhead == total
    assert b.decided_before(len(SPECS)) + b.overhead_s == pytest.approx(
        b.total_s[0], rel=1e-9)


# ------------------------------------------------------ accuracy / state

def _mk_search(tiny_lm, **kw):
    cm, batch = tiny_lm
    ctx = LatencyContext(tokens=1, seq_ctx=256, mode="decode", batch=1)
    scfg = SearchConfig(
        methods="pq", episodes=6, reward=RewardConfig(target_ratio=0.5),
        ddpg=DDPGConfig(warmup_episodes=2, updates_per_episode=2,
                        batch_size=16, buffer_size=256))
    return CompressionSearch(cm, batch, scfg, ctx, **kw)


def test_accuracy_batch_matches_scalar(tiny_lm):
    """vmap-of-jit accuracy over stacked cspecs == per-policy jit."""
    cm, batch = tiny_lm
    rng = np.random.default_rng(7)
    pols = [Policy([map_actions(s, rng.random(3), "pq") for s in cm.specs])
            for _ in range(3)]
    import jax
    jit_acc = jax.jit(lambda cs: cm.accuracy(batch, cs))
    scalar = np.asarray([float(jit_acc(cm.build_cspec(p))) for p in pols])
    stacked = np.asarray(
        cm.accuracy_batch(batch, cm.build_cspec_batch(pols)))
    fused = np.asarray(cm.accuracy_policy_batch(
        batch, stack_policies(cm.specs, pols)))
    np.testing.assert_allclose(stacked, scalar, atol=1e-6)
    np.testing.assert_allclose(fused, scalar, atol=1e-6)


@pytest.mark.parametrize("arch", ["mixtral-8x22b", "mamba2-780m",
                                  "recurrentgemma-2b", "arctic-480b",
                                  "granite-4.0-h-micro"])
def test_accuracy_policy_batch_parity_archs(arch):
    """The traced cspec builder must mirror build_lm_cspec on every
    layer family — moe (incl. dense residual), ssm, rglru, attn."""
    import jax
    from repro.core.compress import CompressibleLM
    from repro.models import model as M
    from repro.models.registry import get_config

    cfg = get_config(arch, smoke=True).replace(param_dtype="float32",
                                               compute_dtype="float32")
    params = M.init(cfg, jax.random.PRNGKey(0))
    cm = CompressibleLM(cfg, params)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                          0, cfg.vocab_size)}
    rng = np.random.default_rng(13)
    pols = [Policy([map_actions(s, rng.random(3), "pq") for s in cm.specs])
            for _ in range(2)]
    jit_acc = jax.jit(lambda cs: cm.accuracy(batch, cs))
    scalar = np.asarray([float(jit_acc(cm.build_cspec(p))) for p in pols])
    fused = np.asarray(cm.accuracy_policy_batch(
        batch, stack_policies(cm.specs, pols)))
    np.testing.assert_allclose(fused, scalar, atol=1e-6)


def test_build_state_batch_matches_scalar(tiny_lm):
    search = _mk_search(tiny_lm)
    rng = np.random.default_rng(11)
    K = 3
    partials = []
    for _ in range(K):
        p = copy.deepcopy(search.ref_policy)
        for i, s in enumerate(search.specs):
            p.cmps[i] = map_actions(s, rng.random(3), "pq")
        partials.append(p)
    prev_a = rng.random((K, 3)).astype(np.float32)
    for t in search.steps:
        cur = policy_latency_batch(
            search.specs, stack_policies(search.specs, partials),
            search.hw, search.ctx, search.cfg.window)
        got = build_state_batch(search.specs, t, cur, search.sens, prev_a,
                                search.ref_lat)
        for j in range(K):
            want = build_state(search.specs, t, partials[j], search.sens,
                               prev_a[j], search.hw, search.ctx,
                               search.ref_lat, search.cfg.window)
            np.testing.assert_allclose(got[j], want, atol=1e-6)


# ---------------------------------------------------------------- replay

@pytest.mark.parametrize("capacity,chunks", [
    (64, (40,)),          # vectorized write, no wraparound
    (32, (20, 20, 20)),   # vectorized writes that wrap the ring
    (16, (40,)),          # oversized batch -> scalar fallback
])
def test_push_batch_equals_sequential_push(capacity, chunks):
    rng = np.random.default_rng(5)
    sd, ad = 6, 2
    one = ReplayBuffer(capacity, sd, ad, seed=0)
    two = ReplayBuffer(capacity, sd, ad, seed=0)
    for n in chunks:
        s = rng.random((n, sd)).astype(np.float32)
        a = rng.random((n, ad)).astype(np.float32)
        r = rng.random(n).astype(np.float32)
        s2 = rng.random((n, sd)).astype(np.float32)
        d = (rng.random(n) > 0.5).astype(np.float32)
        for i in range(n):
            one.push(s[i], a[i], r[i], s2[i], d[i])
        two.push_batch(s, a, r, s2, d)
    assert one.ptr == two.ptr and one.size == two.size
    np.testing.assert_array_equal(one.states, two.states)
    np.testing.assert_array_equal(one.actions, two.actions)
    np.testing.assert_array_equal(one.rewards, two.rewards)
    np.testing.assert_array_equal(one.next_states, two.next_states)
    np.testing.assert_array_equal(one.dones, two.dones)


# ------------------------------------------------------------ the engine

def test_batched_search_policies_legal(tiny_lm):
    search = _mk_search(tiny_lm, batch_size=3)
    for rec in search.run_epoch(0, 1):
        for s, c in zip(search.specs, rec.policy.cmps):
            if s.prunable and s.prune_dim:
                assert c.keep % s.prune_granularity == 0 \
                    or c.keep == s.prune_dim
            if c.mode == "MIX":
                assert s.mix_supported
            if not s.quantizable:
                assert c.mode == "FP32"


def test_batched_search_sigma_schedule(tiny_lm):
    """Each episode in a batch keeps its own sigma/warmup position."""
    search = _mk_search(tiny_lm, batch_size=6)
    recs = search.run_epoch(0, 1)
    want = [search.agent.sigma_at(e) for e in range(6)]
    got = [r.sigma for r in recs]
    np.testing.assert_allclose(got, want, atol=1e-6)


# -------------------------------------------------------- the population

def _mk_population_member(tiny_lm, methods, batch_size=3):
    """Member with action_dim padded to the pq maximum so p/q/pq
    agents stack into one vmappable population."""
    cm, batch = tiny_lm
    ctx = LatencyContext(tokens=1, seq_ctx=256, mode="decode", batch=1)
    scfg = SearchConfig(
        methods=methods, episodes=6, reward=RewardConfig(target_ratio=0.5),
        ddpg=DDPGConfig(warmup_episodes=2, updates_per_episode=2,
                        batch_size=16, buffer_size=256, action_dim=3))
    return CompressionSearch(cm, batch, scfg, ctx, batch_size=batch_size)


def test_population_runs_mixed_methods(tiny_lm):
    """p/q/pq members run as one population (each member's epochs
    update inside their programs); per-member histories keep the
    per-episode semantics (episode order, sigma schedule, legality)."""
    members = [_mk_population_member(tiny_lm, m) for m in ("p", "q", "pq")]
    pop = PopulationSearch(members)
    results = pop.run(episodes=6)
    assert len(results) == 3
    for m, res in zip(members, results):
        assert [r.episode for r in res.history] == list(range(6))
        want = [m.agent.sigma_at(e) for e in range(6)]
        np.testing.assert_allclose([r.sigma for r in res.history], want,
                                   atol=1e-6)
        for rec in res.history:
            assert np.isfinite(rec.reward)
            assert len(rec.policy.cmps) == len(m.specs)
        # no update budget left pending
        assert m._pending_updates == 0
        assert not m._defer_updates
    # padded action dims: all members share the pq agent shape
    assert len({m.agent.cfg.action_dim for m in members}) == 1


def test_population_warmup_matches_independent(tiny_lm):
    """Before any update fires, a population member's rollout equals the
    same search run independently (identical seeds -> identical RNG)."""
    member = _mk_population_member(tiny_lm, "pq")
    solo = _mk_population_member(tiny_lm, "pq")
    pop_recs = PopulationSearch([member]).run(episodes=2)[0].history
    solo_recs = solo.run(episodes=2).history
    for a, b in zip(pop_recs, solo_recs):
        assert a.reward == pytest.approx(b.reward, abs=1e-6)
        assert a.accuracy == pytest.approx(b.accuracy, abs=1e-6)
        assert a.latency_s == pytest.approx(b.latency_s, rel=1e-9)


def test_population_rejects_mismatched_configs(tiny_lm):
    native_pq = _mk_population_member(tiny_lm, "pq")
    cm, batch = tiny_lm
    ctx = LatencyContext(tokens=1, seq_ctx=256, mode="decode", batch=1)
    scfg = SearchConfig(
        methods="p", episodes=6, reward=RewardConfig(target_ratio=0.5),
        ddpg=DDPGConfig(warmup_episodes=2, updates_per_episode=2,
                        batch_size=16, buffer_size=256))   # native dims
    native_p = CompressionSearch(cm, batch, scfg, ctx, batch_size=3)
    with pytest.raises(ValueError):
        PopulationSearch([native_pq, native_p])
    with pytest.raises(ValueError):
        PopulationSearch([])
