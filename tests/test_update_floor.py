"""The DDPG update floor (ISSUE 7): megabatched population updates vs the
``jit(vmap(update_chunk))`` parity reference, the fused MLP/Polyak kernel
routes, dispatch counting, and the paper's init distributions.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ddpg
from repro.core.ddpg import (DDPGConfig, agent_init, _mlp, _mlp_init,
                             actor_forward, critic_forward, polyak_update,
                             population_update_chunk,
                             population_update_chunk_megabatched,
                             population_update_chunk_vmap, tree_stack)
from repro.core.replay import DeviceReplay

# small nets + batch keep these tier-1 fast; shapes stay 3-layer so the
# megabatched step covers them
CFG = dict(state_dim=10, action_dim=6, hidden=(32, 24), batch_size=16)


def _population(P, mixed=False, seed=0, cap=120, fill=90, **over):
    cfg = DDPGConfig(**{**CFG, **over})
    rng = np.random.default_rng(seed)
    states, replays = [], []
    for p in range(P):
        st = agent_init(cfg, jax.random.PRNGKey(seed + p))
        n = fill - (17 * (p % 3) if mixed else 0)   # mixed sizes + ptrs
        rep = DeviceReplay(cap, cfg.state_dim, cfg.action_dim)
        for _ in range(n):
            rep.push(rng.standard_normal(cfg.state_dim).astype(np.float32),
                     rng.uniform(size=cfg.action_dim).astype(np.float32),
                     float(rng.standard_normal()),
                     rng.standard_normal(cfg.state_dim).astype(np.float32),
                     float(rng.integers(0, 2)))
        states.append(st)
        replays.append(rep.data)
    return cfg, tree_stack(states), tree_stack(replays)


def _max_err(a, b):
    errs = jax.tree.map(lambda x, y: float(jnp.max(jnp.abs(x - y))), a, b)
    return max(jax.tree.leaves(errs))


# ------------------- megabatched vs vmap parity ----------------------------

@pytest.mark.parametrize("P", [1, 3, 8])
def test_megabatched_matches_vmap(P):
    cfg, states, replays = _population(P, mixed=True, seed=P)
    n = 5
    s_ref, (lc_ref, la_ref) = population_update_chunk_vmap(
        cfg, states, replays, n)
    s_mb, (lc_mb, la_mb) = population_update_chunk_megabatched(
        cfg, states, replays, n)
    assert _max_err(s_ref, s_mb) <= 1e-5
    assert float(jnp.max(jnp.abs(lc_ref - lc_mb))) <= 1e-5
    assert float(jnp.max(jnp.abs(la_ref - la_mb))) <= 1e-5
    # identical key streams -> future sampling stays bit-equal
    assert bool(jnp.all(s_ref.key == s_mb.key))


def test_megabatched_multi_chunk_stays_on_reference_trajectory():
    """Three consecutive chunks through each path stay within tolerance:
    errors don't compound past the gate."""
    cfg, states, replays = _population(4, mixed=True, seed=42)
    s_ref, s_mb = states, states
    for _ in range(3):
        s_ref, _ = population_update_chunk_vmap(cfg, s_ref, replays, 2)
        s_mb, _ = population_update_chunk_megabatched(
            cfg, s_mb, replays, 2)
    assert _max_err(s_ref, s_mb) <= 1e-4


def test_router_default_and_vmap_toggle(monkeypatch):
    """The router takes the megabatched path for the paper trunk and the
    vmap reference under GALEN_POP_UPDATE=vmap — verified by counting
    executions of each compiled entry."""
    calls = {"mega": 0, "vmap": 0}
    real_mega = ddpg._population_update_chunk_mega_jit
    real_vmap = ddpg._population_update_chunk_jit

    def count_mega(*a, **k):
        calls["mega"] += 1
        return real_mega(*a, **k)

    def count_vmap(*a, **k):
        calls["vmap"] += 1
        return real_vmap(*a, **k)

    monkeypatch.setattr(ddpg, "_population_update_chunk_mega_jit",
                        count_mega)
    monkeypatch.setattr(ddpg, "_population_update_chunk_jit", count_vmap)
    monkeypatch.delenv("GALEN_POP_UPDATE", raising=False)

    cfg, states, replays = _population(2)
    population_update_chunk(cfg, states, replays, 2)
    assert calls == {"mega": 1, "vmap": 0}

    monkeypatch.setenv("GALEN_POP_UPDATE", "vmap")
    population_update_chunk(cfg, states, replays, 2)
    assert calls == {"mega": 1, "vmap": 1}


def test_router_falls_back_for_non_paper_trunk(monkeypatch):
    """Hidden depths the hand-written step doesn't cover route to vmap."""
    calls = {"vmap": 0}
    real_vmap = ddpg._population_update_chunk_jit

    def count_vmap(*a, **k):
        calls["vmap"] += 1
        return real_vmap(*a, **k)

    monkeypatch.setattr(ddpg, "_population_update_chunk_jit", count_vmap)
    monkeypatch.delenv("GALEN_POP_UPDATE", raising=False)
    cfg, states, replays = _population(2, hidden=(32, 24, 16))
    population_update_chunk(cfg, states, replays, 1)
    assert calls["vmap"] == 1


def test_megabatched_is_one_dispatch_per_chunk(monkeypatch):
    """The whole population chunk is ONE execution of the megabatched
    compiled entry — and zero executions of the per-member/vmap ones."""
    counts = {"mega": 0, "mega_donate": 0, "vmap": 0, "member": 0}
    reals = {
        "mega": ddpg._population_update_chunk_mega_jit,
        "mega_donate": ddpg._population_update_chunk_mega_donate_jit,
        "vmap": ddpg._population_update_chunk_jit,
        "member": ddpg._update_chunk_jit,
    }

    def wrap(name):
        def f(*a, **k):
            counts[name] += 1
            return reals[name](*a, **k)
        return f

    monkeypatch.setattr(ddpg, "_population_update_chunk_mega_jit",
                        wrap("mega"))
    monkeypatch.setattr(ddpg, "_population_update_chunk_mega_donate_jit",
                        wrap("mega_donate"))
    monkeypatch.setattr(ddpg, "_population_update_chunk_jit",
                        wrap("vmap"))
    monkeypatch.setattr(ddpg, "_update_chunk_jit", wrap("member"))
    monkeypatch.delenv("GALEN_POP_UPDATE", raising=False)

    cfg, states, replays = _population(4)
    for i in range(3):
        states, _ = population_update_chunk(cfg, states, replays, 2)
        assert counts == {"mega": i + 1, "mega_donate": 0, "vmap": 0,
                          "member": 0}


def test_megabatched_donation_matches_and_consumes():
    cfg, states, replays = _population(3, mixed=True)
    ref, _ = population_update_chunk_megabatched(cfg, states, replays, 3)
    cfg2, states2, replays2 = _population(3, mixed=True)
    don, _ = population_update_chunk_megabatched(cfg2, states2, replays2, 3,
                                                 donate=True)
    assert _max_err(ref, don) == 0.0


# ----------------------- kernel-path parity --------------------------------

def test_mlp_kernel_route_matches_reference(monkeypatch):
    """GALEN_MLP_KERNEL=1 (fused Pallas forward + custom_vjp backward)
    agrees with the reference ``_mlp`` loop for both trunk shapes."""
    cfg = DDPGConfig(**CFG)
    st = agent_init(cfg, jax.random.PRNGKey(0))
    s = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.state_dim))
    a = jax.random.uniform(jax.random.PRNGKey(2), (16, cfg.action_dim))

    monkeypatch.setenv("GALEN_MLP_KERNEL", "0")
    y_ref = actor_forward(st.actor, s)
    q_ref = critic_forward(st.critic, s, a)
    ga_ref = jax.grad(lambda p: jnp.sum(actor_forward(p, s) ** 2))(st.actor)
    gc_ref = jax.grad(
        lambda p: jnp.sum(critic_forward(p, s, a) ** 2))(st.critic)

    monkeypatch.setenv("GALEN_MLP_KERNEL", "1")
    y_k = actor_forward(st.actor, s)
    q_k = critic_forward(st.critic, s, a)
    ga_k = jax.grad(lambda p: jnp.sum(actor_forward(p, s) ** 2))(st.actor)
    gc_k = jax.grad(
        lambda p: jnp.sum(critic_forward(p, s, a) ** 2))(st.critic)

    assert float(jnp.max(jnp.abs(y_k - y_ref))) <= 1e-5
    assert float(jnp.max(jnp.abs(q_k - q_ref))) <= 1e-5
    assert _max_err(ga_k, ga_ref) <= 1e-5
    assert _max_err(gc_k, gc_ref) <= 1e-5


def test_polyak_kernel_route_matches_reference(monkeypatch):
    cfg = DDPGConfig(**CFG)
    st = agent_init(cfg, jax.random.PRNGKey(3))
    monkeypatch.setenv("GALEN_MLP_KERNEL", "0")
    t_ref = polyak_update(st.target_actor, st.actor, cfg.tau)
    monkeypatch.setenv("GALEN_MLP_KERNEL", "1")
    t_k = polyak_update(st.target_actor, st.actor, cfg.tau)
    assert _max_err(t_k, t_ref) <= 1e-6


def test_mlp_route_guard_rejects_unsupported():
    """Non-3-layer, non-2D, and exotic final activations stay on the
    reference path regardless of the env toggle."""
    two = _mlp_init(jax.random.PRNGKey(0), (8, 8, 8))
    x2 = jnp.ones((4, 8))
    assert not ddpg._mlp_kernel_route(two, x2, None)
    three = _mlp_init(jax.random.PRNGKey(0), (8, 8, 8, 8))
    assert not ddpg._mlp_kernel_route(three, jnp.ones((8,)), None)
    assert not ddpg._mlp_kernel_route(three, x2, jnp.tanh)


# -------------------- init distribution properties -------------------------

def test_mlp_init_final_layer_is_paper_uniform():
    """Paper init: final layer U(-3e-3, 3e-3), hidden layers U(+-1/sqrt(a)),
    zero biases. Pinned so kernel-path refactors can't drift it."""
    dims = (10, 400, 300, 6)
    params = _mlp_init(jax.random.PRNGKey(0), dims)
    assert len(params) == 3
    for i, (l, (a, b)) in enumerate(zip(params, zip(dims[:-1], dims[1:]))):
        assert l["w"].shape == (a, b)
        assert l["b"].shape == (b,)
        assert l["w"].dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(l["b"]), 0.0)
        lim = 3e-3 if i == 2 else 1.0 / np.sqrt(a)
        w = np.asarray(l["w"])
        assert np.abs(w).max() <= lim            # bounded by the limit
        assert np.abs(w).max() >= 0.95 * lim     # and actually fills it
        assert abs(w.mean()) <= 0.1 * lim        # centered
        # uniform, not gaussian: the sample variance of U(-lim, lim) is
        # lim^2/3; a normal clipped to the same max would differ
        np.testing.assert_allclose(w.var(), lim ** 2 / 3.0, rtol=0.1)


def test_mlp_init_final_scale_only_affects_last_layer():
    p1 = _mlp_init(jax.random.PRNGKey(1), (10, 32, 24, 4),
                   final_scale=3e-3)
    p2 = _mlp_init(jax.random.PRNGKey(1), (10, 32, 24, 4),
                   final_scale=1e-1)
    for l1, l2 in zip(p1[:-1], p2[:-1]):
        np.testing.assert_array_equal(np.asarray(l1["w"]),
                                      np.asarray(l2["w"]))
    w1 = np.abs(np.asarray(p1[-1]["w"])).max()
    w2 = np.abs(np.asarray(p2[-1]["w"])).max()
    assert w1 <= 3e-3 and w2 > 3e-3


def test_agent_init_uses_paper_final_scale():
    cfg = DDPGConfig(**CFG)
    st = agent_init(cfg, jax.random.PRNGKey(4))
    for net in (st.actor, st.critic):
        assert np.abs(np.asarray(net[-1]["w"])).max() <= 3e-3
        assert np.abs(np.asarray(net[0]["w"])).max() > 3e-3
