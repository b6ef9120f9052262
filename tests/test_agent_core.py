"""Functional agent core: scalar-update parity, fused-chunk parity,
vmapped population parity, and DeviceReplay/ReplayBuffer equivalence.

The contract proved here (mirrors PR 1's rollout-parity suite):

  * ``update_step`` == the legacy ``DDPGAgent.update`` host path given
    the same sampled batch (losses and resulting params within 1e-5);
  * ``update_chunk`` == n sequential legacy updates when the legacy
    path is fed exactly the batches the chunk's in-scan sampler draws;
  * ``jit(vmap(update_chunk))`` over a stacked population == P
    independent single-agent chunks;
  * ``DeviceReplay`` ring semantics == host ``ReplayBuffer`` (the
    reference), including wraparound and oversized batches, and both
    sample deterministically under a fixed seed.
"""
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.core.ddpg import (AgentState, DDPGAgent, DDPGConfig, agent_act,
                             agent_init, chunk_sample_keys,
                             population_update_chunk, tree_index, tree_stack,
                             update_chunk, update_step)
from repro.core.replay import (DeviceReplay, ReplayBuffer,
                               device_replay_sample)
from repro.core.search import SearchConfig

CFG = DDPGConfig(state_dim=6, action_dim=2, hidden=(16, 16), batch_size=8,
                 buffer_size=64, warmup_episodes=0, updates_per_episode=4)


def _fill(rng, *replays, n=40, state_dim=6, action_dim=2):
    """Push the same n random transitions into every buffer given."""
    for i in range(n):
        s = rng.random(state_dim).astype(np.float32)
        a = rng.random(action_dim).astype(np.float32)
        r = float(rng.standard_normal())
        s2 = rng.random(state_dim).astype(np.float32)
        d = float(i % 10 == 9)
        for rep in replays:
            rep.push(s, a, r, s2, d)


class _ScriptedReplay:
    """Host replay stub that replays a fixed sequence of batches — lets
    the legacy ``DDPGAgent.update`` consume exactly the batches an
    ``update_chunk`` scan drew."""

    def __init__(self, batches):
        self.batches = list(batches)
        self.i = 0

    def sample(self, batch_size):
        b = self.batches[self.i]
        self.i += 1
        return b

    def __len__(self):
        return 10 ** 9


def _params_close(a, b, atol):
    ja, jb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(ja) == len(jb)
    for x, y in zip(ja, jb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol)


# ------------------------------------------------------ scalar parity

@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=5, deadline=None)
def test_update_step_matches_legacy_update(seed):
    """One ``update_step`` == one legacy ``DDPGAgent.update`` on the
    same sampled batch: same losses, same resulting parameters."""
    rng = np.random.default_rng(seed)
    legacy = DDPGAgent(CFG, seed=int(seed % 1000))
    legacy.observe_states(rng.standard_normal((32, 6)).astype(np.float32))
    batch = (rng.random((8, 6)).astype(np.float32),
             rng.random((8, 2)).astype(np.float32),
             rng.standard_normal(8).astype(np.float32),
             rng.random((8, 6)).astype(np.float32),
             (rng.random(8) > 0.8).astype(np.float32))
    st0 = legacy.state_for_dispatch()
    lc0, la0 = legacy.update(_ScriptedReplay([batch]))

    st1, (lc1, la1) = jax.jit(update_step, static_argnums=0)(
        CFG, st0, tuple(jnp.asarray(x) for x in batch))
    assert float(lc1) == pytest.approx(lc0, abs=1e-5)
    assert float(la1) == pytest.approx(la0, abs=1e-5)
    _params_close(st1.actor, legacy.actor, 1e-5)
    _params_close(st1.critic, legacy.critic, 1e-5)
    _params_close(st1.target_actor, legacy.target_actor, 1e-5)
    _params_close(st1.target_critic, legacy.target_critic, 1e-5)
    assert float(st1.reward_ma) == pytest.approx(legacy.reward_ma, abs=1e-5)


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=5, deadline=None)
def test_update_chunk_matches_sequential_legacy(seed):
    """A fused n-step chunk (in-scan sampling included) == n sequential
    legacy updates fed the exact batches the chunk draws."""
    n = 4
    rng = np.random.default_rng(seed)
    chunky = DDPGAgent(CFG, seed=int(seed % 1000))
    legacy = DDPGAgent(CFG, seed=int(seed % 1000))
    dev = DeviceReplay(CFG.buffer_size, 6, 2, seed=0)
    _fill(rng, dev)
    obs = rng.standard_normal((32, 6)).astype(np.float32)
    chunky.observe_states(obs)
    legacy.observe_states(obs)

    # replay the chunk's PRNG stream to extract the batches it will draw
    _, keys = chunk_sample_keys(chunky.state.key, n)
    batches = [
        tuple(np.asarray(x)
              for x in device_replay_sample(dev.data, k, CFG.batch_size))
        for k in keys]

    lcs, las = chunky.update_chunk(dev, n)
    scripted = _ScriptedReplay(batches)
    ref = np.asarray([legacy.update(scripted) for _ in range(n)])
    np.testing.assert_allclose(lcs, ref[:, 0], atol=1e-5)
    np.testing.assert_allclose(las, ref[:, 1], atol=1e-5)
    _params_close(chunky.actor, legacy.actor, 1e-5)
    _params_close(chunky.critic, legacy.critic, 1e-5)
    _params_close(chunky.target_actor, legacy.target_actor, 1e-5)
    _params_close(chunky.target_critic, legacy.target_critic, 1e-5)
    assert chunky.reward_ma == pytest.approx(legacy.reward_ma, abs=1e-5)


def test_update_chunk_deterministic():
    """Same state + same replay -> same chunk results (and the carry
    key advances, so the next chunk draws a fresh stream)."""
    rng = np.random.default_rng(0)
    a1, a2 = DDPGAgent(CFG, seed=5), DDPGAgent(CFG, seed=5)
    d1 = DeviceReplay(CFG.buffer_size, 6, 2, seed=1)
    d2 = DeviceReplay(CFG.buffer_size, 6, 2, seed=1)
    _fill(rng, d1, d2)
    l1 = a1.update_chunk(d1, 3)
    l2 = a2.update_chunk(d2, 3)
    np.testing.assert_array_equal(l1[0], l2[0])
    l1b = a1.update_chunk(d1, 3)
    assert not np.array_equal(l1[0], l1b[0])


# --------------------------------------------------- population parity

@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=3, deadline=None)
def test_population_chunk_matches_independent(seed):
    """jit(vmap(update_chunk)) over P stacked agents == P independent
    single-agent chunks (params and losses within 1e-5)."""
    P, n = 3, 3
    rng = np.random.default_rng(seed)
    agents, devs = [], []
    for p in range(P):
        ag = DDPGAgent(CFG, seed=int(seed % 1000) + p)
        dv = DeviceReplay(CFG.buffer_size, 6, 2, seed=p)
        _fill(rng, dv)           # different transitions per member
        ag.observe_states(rng.standard_normal((16, 6)).astype(np.float32))
        agents.append(ag)
        devs.append(dv)

    states = tree_stack([ag.state_for_dispatch() for ag in agents])
    datas = tree_stack([dv.data for dv in devs])
    pop_states, (pop_lc, _) = population_update_chunk(CFG, states, datas, n)

    for i, (ag, dv) in enumerate(zip(agents, devs)):
        lc, _la = ag.update_chunk(dv, n)       # independent fused chunk
        np.testing.assert_allclose(np.asarray(pop_lc)[i], lc, atol=1e-5)
        member = tree_index(pop_states, i)
        _params_close(member.actor, ag.actor, 1e-5)
        _params_close(member.critic, ag.critic, 1e-5)
        _params_close(member.target_actor, ag.target_actor, 1e-5)
        assert float(member.reward_ma) == pytest.approx(ag.reward_ma,
                                                        abs=1e-5)


# ------------------------------------------------- device replay parity

@pytest.mark.parametrize("capacity,chunks", [
    (64, (40,)),          # vectorized write, no wraparound
    (32, (20, 20, 20)),   # vectorized writes that wrap the ring
    (16, (40,)),          # oversized batch -> tail write
    (16, (7, 40, 9)),     # oversized batch mid-stream, nonzero ptr
])
def test_device_replay_matches_host(capacity, chunks):
    """DeviceReplay ring writes land exactly where the host reference
    puts them, for single pushes, bulk, wraparound and oversized."""
    rng = np.random.default_rng(5)
    sd, ad = 6, 2
    host = ReplayBuffer(capacity, sd, ad, seed=0)
    dev = DeviceReplay(capacity, sd, ad, seed=0)
    for n in chunks:
        s = rng.random((n, sd)).astype(np.float32)
        a = rng.random((n, ad)).astype(np.float32)
        r = rng.random(n).astype(np.float32)
        s2 = rng.random((n, sd)).astype(np.float32)
        d = (rng.random(n) > 0.5).astype(np.float32)
        host.push_batch(s, a, r, s2, d)
        dev.push_batch(s, a, r, s2, d)
    assert host.ptr == dev.ptr == int(dev.data.ptr)
    assert host.size == dev.size == int(dev.data.size) == len(dev)
    np.testing.assert_array_equal(host.states, np.asarray(dev.data.states))
    np.testing.assert_array_equal(host.actions, np.asarray(dev.data.actions))
    np.testing.assert_array_equal(host.rewards, np.asarray(dev.data.rewards))
    np.testing.assert_array_equal(host.next_states,
                                  np.asarray(dev.data.next_states))
    np.testing.assert_array_equal(host.dones, np.asarray(dev.data.dones))


@pytest.mark.parametrize("cls", [ReplayBuffer, DeviceReplay])
def test_replay_sample_deterministic_under_seed(cls):
    """Same seed + same transitions in -> same sample stream out, for
    both the host reference and the device buffer."""
    rng = np.random.default_rng(9)
    b1 = cls(32, 4, 1, seed=7)
    b2 = cls(32, 4, 1, seed=7)
    _fill(rng, b1, b2, n=48, state_dim=4, action_dim=1)
    for _ in range(3):
        s1 = b1.sample(8)
        s2 = b2.sample(8)
        for x, y in zip(s1, s2):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # and the stream advances: consecutive draws differ
    nxt = b1.sample(8)
    assert not all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(s1, nxt))


def test_replay_wraparound_oldest_evicted():
    for cls in (ReplayBuffer, DeviceReplay):
        buf = cls(4, 2, 1, seed=0)
        for i in range(6):
            buf.push(np.full(2, i, np.float32), np.asarray([i], np.float32),
                     float(i), np.full(2, i + 1, np.float32), i == 5)
        assert len(buf) == 4
        s, a, r, s2, d = buf.sample(16)
        assert set(np.unique(np.asarray(r))) <= {2.0, 3.0, 4.0, 5.0}


# ------------------------------------------------------- pure act / cfg

def test_agent_act_pure_matches_host_mean():
    """sigma=0: the pure jax act == the host numpy rollout forward."""
    agent = DDPGAgent(CFG, seed=3)
    rng = np.random.default_rng(0)
    agent.observe_states(rng.standard_normal((64, 6)).astype(np.float32))
    s = rng.standard_normal(6).astype(np.float32)
    host = agent.act(s, sigma=0.0)
    pure = np.asarray(agent_act(CFG, agent.state_for_dispatch(),
                                jnp.asarray(s), jax.random.PRNGKey(0), 0.0))
    np.testing.assert_allclose(pure, host, atol=1e-5)


def test_agent_act_pure_bounded():
    agent = DDPGAgent(CFG, seed=3)
    s = np.random.default_rng(1).standard_normal(6).astype(np.float32)
    for i, sigma in enumerate((0.1, 0.5, 2.0)):
        a = np.asarray(agent_act(CFG, agent.state, jnp.asarray(s),
                                 jax.random.PRNGKey(i), sigma))
        assert a.shape == (2,)
        assert np.all((a >= 0) & (a <= 1))


def test_agent_state_is_pytree():
    st = agent_init(CFG, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(st)
    assert all(hasattr(x, "dtype") for x in leaves)
    stacked = tree_stack([st, st])
    assert stacked.norm_mean.shape == (2, CFG.state_dim)
    back = tree_index(stacked, 1)
    np.testing.assert_array_equal(np.asarray(back.norm_mean),
                                  np.asarray(st.norm_mean))


def test_search_config_reward_default_not_shared():
    """Regression: the RewardConfig default must not be a shared
    mutable instance across SearchConfig objects."""
    a, b = SearchConfig(), SearchConfig()
    assert a.reward == b.reward
    assert a.reward is not b.reward
