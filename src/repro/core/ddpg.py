"""DDPG (Lillicrap et al. 2015) in pure JAX — the paper's agent core.

Paper hyperparameters: actor/critic MLPs with hidden (400, 300); sigmoid-
bounded actions in [0,1]; Adam lr 1e-4 (actor) / 1e-3 (critic),
β1=0.9 β2=0.999; γ=0.99; batch 128; replay 2000; exploration via truncated
normal σ0=0.5, decay 0.95/episode; rewards in each sampled batch normalized
with a moving average; states standardized with running mean/var estimates.

Layout
------
The agent is a *functional* subsystem: all learnable/learning state lives
in an ``AgentState`` pytree (actor/critic/targets/Adam moments/running-norm
stats/reward moving average/PRNG key) manipulated by pure functions:

  * ``agent_init(cfg, key)``                 — build a fresh state;
  * ``agent_act(cfg, st, s, key, sigma)``    — pure jax acting (truncated-
    normal exploration), the traceable twin of the host rollout path;
  * ``update_step(cfg, st, batch)``          — one critic/actor/target
    update on an explicit batch, including the per-step reward-moving-
    average advance and state standardization (normalizer stats are
    *frozen* inside the step — they only move at rollout boundaries);
  * ``update_chunk(cfg, st, replay, n)``     — ``lax.scan`` of n update
    steps with in-scan uniform replay sampling: one jitted dispatch, one
    host sync for the losses, instead of n sample+dispatch round-trips;
  * ``population_update_chunk(cfg, sts, replays, n)`` — ``jit(vmap)`` of
    the chunk over a stacked population of P agent states + buffers, so
    p/q/pq agents (or one agent per hardware target) share every update
    dispatch.

``DDPGAgent`` remains as a thin compatibility shim over ``AgentState``:
``act`` is the host-numpy actor (the reference ``agent_act`` is tested
against), ``update(replay)`` keeps the original host-sampled scalar
semantics, and ``update_chunk(replay, n)`` dispatches the fused scan.
Host-authoritative pieces (running norm, reward-MA between dispatches)
are synced into the pytree right before each fused dispatch.
"""
from __future__ import annotations

import math
import os
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.replay import DeviceReplayData, device_replay_sample

_mlp_ops = None         # lazy kernels.ops handle (kernel package must not
                        # load at agent-import; mirrors core.quantization)


@dataclass(frozen=True)
class DDPGConfig:
    state_dim: int = 16
    action_dim: int = 1
    hidden: Tuple[int, int] = (400, 300)
    actor_lr: float = 1e-4
    critic_lr: float = 1e-3
    gamma: float = 0.99
    tau: float = 0.01                  # soft target update
    batch_size: int = 128
    buffer_size: int = 2000
    sigma0: float = 0.5
    sigma_decay: float = 0.95
    warmup_episodes: int = 10
    updates_per_episode: int = 32
    reward_ma_decay: float = 0.95      # moving-average reward normalizer


def _mlp_init(key, dims, final_scale=3e-3):
    params = []
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        key, k = jax.random.split(key)
        lim = final_scale if i == len(dims) - 2 else 1.0 / math.sqrt(a)
        params.append({
            "w": jax.random.uniform(k, (a, b), jnp.float32, -lim, lim),
            "b": jnp.zeros((b,), jnp.float32)})
    return params


def _mlp_kernel_route(params, x, final_act) -> bool:
    """True when this MLP forward should run through the fused Pallas
    kernel (``kernels.ops.fused_mlp3``): the kernel implements exactly
    the paper's 3-layer trunk on a 2D batch with a linear or sigmoid
    head, and only a TPU backend compiles it to Mosaic — everywhere else
    the reference jnp chain stays the default. ``GALEN_MLP_KERNEL=1``
    forces the kernel (interpreted off-TPU, for parity tests);
    ``GALEN_MLP_KERNEL=0`` forces the reference path even on TPU. The
    route is resolved at trace time, mirroring ``GALEN_FQ_KERNEL``."""
    if len(params) != 3 or x.ndim != 2:
        return False
    if final_act is not None and final_act is not jax.nn.sigmoid:
        return False
    v = os.environ.get("GALEN_MLP_KERNEL")
    if v is not None:
        return v == "1"
    return jax.default_backend() == "tpu"


def _mlp(params, x, final_act=None):
    if _mlp_kernel_route(params, x, final_act):
        global _mlp_ops
        if _mlp_ops is None:
            from repro.kernels import ops
            _mlp_ops = ops
        final = "sigmoid" if final_act is jax.nn.sigmoid else "linear"
        return _mlp_ops.fused_mlp3(params, x, final=final)
    for i, layer in enumerate(params):
        x = x @ layer["w"] + layer["b"]
        if i < len(params) - 1:
            x = jax.nn.relu(x)
    return final_act(x) if final_act else x


def actor_forward(params, state):
    return _mlp(params, state, jax.nn.sigmoid)   # actions in [0, 1]


def critic_forward(params, state, action):
    x = jnp.concatenate([state, action], axis=-1)
    return _mlp(params, x)[..., 0]


# --- minimal Adam (self-contained; the training stack has its own) ---

def adam_init(params):
    z = jax.tree.map(jnp.zeros_like, params)
    return {"m": z, "v": jax.tree.map(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32)}


def adam_step(params, grads, st, lr, b1=0.9, b2=0.999, eps=1e-8):
    t = st["t"] + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, st["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, st["v"], grads)
    mh = jax.tree.map(lambda m: m / (1 - b1 ** t), m)
    vh = jax.tree.map(lambda v: v / (1 - b2 ** t), v)
    params = jax.tree.map(lambda p, m, v: p - lr * m / (jnp.sqrt(v) + eps),
                          params, mh, vh)
    return params, {"m": m, "v": v, "t": t}


def polyak_update(target, online, tau):
    """Soft-target update ``(1 - tau) * target + tau * online``. Routed
    like ``_mlp``: the flat single-pass Pallas kernel on TPU (or under
    ``GALEN_MLP_KERNEL=1``), the per-leaf tree map everywhere else."""
    v = os.environ.get("GALEN_MLP_KERNEL")
    use_kernel = v == "1" if v is not None \
        else jax.default_backend() == "tpu"
    if use_kernel:
        global _mlp_ops
        if _mlp_ops is None:
            from repro.kernels import ops
            _mlp_ops = ops
        return _mlp_ops.fused_polyak(target, online, tau)
    return jax.tree.map(lambda t, p: (1 - tau) * t + tau * p,
                        target, online)


@dataclass
class RunningNorm:
    """Standardize states with running mean/var (paper §Proposed Agents)."""
    dim: int
    count: float = 1e-4
    mean: np.ndarray = None
    var: np.ndarray = None

    def __post_init__(self):
        if self.mean is None:
            self.mean = np.zeros(self.dim, np.float32)
        if self.var is None:
            self.var = np.ones(self.dim, np.float32)

    def update(self, x: np.ndarray):
        x = np.atleast_2d(x)
        bc, bm, bv = x.shape[0], x.mean(0), x.var(0)
        delta = bm - self.mean
        tot = self.count + bc
        self.mean = self.mean + delta * bc / tot
        m_a = self.var * self.count
        m_b = bv * bc
        self.var = (m_a + m_b + delta ** 2 * self.count * bc / tot) / tot
        self.count = tot

    def normalize(self, x: np.ndarray) -> np.ndarray:
        return (x - self.mean) / np.sqrt(self.var + 1e-8)


# ===========================================================================
# Functional core
# ===========================================================================

class AgentState(NamedTuple):
    """Everything one DDPG agent learns or consumes while learning.

    A pure pytree: scans carry it, ``vmap`` stacks P of them into a
    population, and the host shim treats it as the single source of
    truth for parameters between dispatches.
    """
    actor: list
    critic: list
    target_actor: list
    target_critic: list
    opt_a: dict
    opt_c: dict
    norm_count: jnp.ndarray     # () f32   running-norm sample count
    norm_mean: jnp.ndarray      # (state_dim,) f32
    norm_var: jnp.ndarray       # (state_dim,) f32
    reward_ma: jnp.ndarray      # () f32   moving-average reward
    reward_ma_init: jnp.ndarray  # () f32  0 = uninitialized
    key: jnp.ndarray            # PRNG key (drives in-scan sampling)


def agent_init(cfg: DDPGConfig, key) -> AgentState:
    k1, k2, key = jax.random.split(key, 3)
    dims_a = (cfg.state_dim,) + cfg.hidden + (cfg.action_dim,)
    dims_c = (cfg.state_dim + cfg.action_dim,) + cfg.hidden + (1,)
    actor = _mlp_init(k1, dims_a)
    critic = _mlp_init(k2, dims_c)
    return AgentState(
        actor=actor, critic=critic,
        target_actor=jax.tree.map(jnp.copy, actor),
        target_critic=jax.tree.map(jnp.copy, critic),
        opt_a=adam_init(actor), opt_c=adam_init(critic),
        norm_count=jnp.asarray(1e-4, jnp.float32),
        norm_mean=jnp.zeros((cfg.state_dim,), jnp.float32),
        norm_var=jnp.ones((cfg.state_dim,), jnp.float32),
        reward_ma=jnp.zeros((), jnp.float32),
        reward_ma_init=jnp.zeros((), jnp.float32),
        key=key)


def agent_act(cfg: DDPGConfig, st: AgentState, s, key, sigma):
    """Pure acting: standardized state -> actor -> truncated normal.

    Mirrors the host rejection sampler: 16 candidate draws, first
    in-bounds one wins, else the first draw clipped to [0, 1].
    """
    s = (s - st.norm_mean) / jnp.sqrt(st.norm_var + 1e-8)
    mu = actor_forward(st.actor, s)
    cand = mu + sigma * jax.random.normal(key, (16,) + mu.shape, jnp.float32)
    ok = jnp.all((cand >= 0.0) & (cand <= 1.0), axis=-1)
    first = jnp.argmax(ok)
    noisy = jnp.where(jnp.any(ok), cand[first], jnp.clip(cand[0], 0.0, 1.0))
    return jnp.where(sigma > 0.0, noisy, mu)


def agent_act_batch(cfg: DDPGConfig, st: AgentState, states, key, sigmas,
                    warmup):
    """Pure batched acting for the fused rollout scan: K states -> K
    actions in one traceable block.

    Per-row semantics match the engines' host path: warmup rows draw
    uniform [0,1) actions; live rows run the standardized actor with
    per-row truncated-normal exploration (16-candidate rejection via
    ``agent_act``). All randomness comes from ``key`` — one split for
    the warmup uniforms, then one subkey per row — so host code (parity
    tests, the numpy reference) can replay the exact draws.
    """
    K = states.shape[0]
    k_uni, k_act = jax.random.split(key)
    uniform = jax.random.uniform(k_uni, (K, cfg.action_dim), jnp.float32)
    keys = jax.random.split(k_act, K)
    acted = jax.vmap(lambda s, k, sig: agent_act(cfg, st, s, k, sig))(
        states, keys, sigmas)
    return jnp.where(jnp.asarray(warmup)[:, None], uniform, acted)


def observe_states_pure(st: AgentState, states) -> AgentState:
    """Advance the running-norm stats from an (N, state_dim) block — the
    traced twin of ``RunningNorm.update`` (same parallel-variance
    formula, f32), so the epoch scan can move the normalizer at batch
    boundaries without the host."""
    x = jnp.asarray(states, jnp.float32)
    bc = jnp.asarray(x.shape[0], jnp.float32)
    bm, bv = x.mean(axis=0), x.var(axis=0)
    delta = bm - st.norm_mean
    tot = st.norm_count + bc
    mean = st.norm_mean + delta * bc / tot
    m_a = st.norm_var * st.norm_count
    m_b = bv * bc
    var = (m_a + m_b + delta ** 2 * st.norm_count * bc / tot) / tot
    return st._replace(norm_count=tot, norm_mean=mean, norm_var=var)


def ddpg_step(cfg: DDPGConfig, actor, critic, t_actor, t_critic,
              opt_a, opt_c, batch):
    """One critic + actor + soft-target update on a prepared batch
    (states already standardized, rewards already centered)."""
    s, a, r, s2, done = batch

    def critic_loss(cp):
        a2 = actor_forward(t_actor, s2)
        q_target = r + cfg.gamma * (1.0 - done) * critic_forward(
            t_critic, s2, a2)
        q = critic_forward(cp, s, a)
        return jnp.mean((q - jax.lax.stop_gradient(q_target)) ** 2)

    lc, gc = jax.value_and_grad(critic_loss)(critic)
    critic, opt_c = adam_step(critic, gc, opt_c, cfg.critic_lr)

    def actor_loss(ap):
        return -jnp.mean(critic_forward(critic, s, actor_forward(ap, s)))

    la, ga = jax.value_and_grad(actor_loss)(actor)
    actor, opt_a = adam_step(actor, ga, opt_a, cfg.actor_lr)

    t_actor = polyak_update(t_actor, actor, cfg.tau)
    t_critic = polyak_update(t_critic, critic, cfg.tau)
    return actor, critic, t_actor, t_critic, opt_a, opt_c, lc, la


def update_step(cfg: DDPGConfig, st: AgentState, batch):
    """One full scalar-semantics update on an explicit sampled batch:
    reward-MA advance -> reward centering -> state standardization with
    the snapshot norm stats -> ``ddpg_step``."""
    s, a, r, s2, done = batch
    batch_mean = jnp.mean(r)
    d = cfg.reward_ma_decay
    ma = jnp.where(st.reward_ma_init > 0.0,
                   d * st.reward_ma + (1.0 - d) * batch_mean, batch_mean)
    r = r - ma
    inv = 1.0 / jnp.sqrt(st.norm_var + 1e-8)
    s = (s - st.norm_mean) * inv
    s2 = (s2 - st.norm_mean) * inv
    actor, critic, t_actor, t_critic, opt_a, opt_c, lc, la = ddpg_step(
        cfg, st.actor, st.critic, st.target_actor, st.target_critic,
        st.opt_a, st.opt_c, (s, a, r, s2, done))
    st = st._replace(actor=actor, critic=critic, target_actor=t_actor,
                     target_critic=t_critic, opt_a=opt_a, opt_c=opt_c,
                     reward_ma=ma.astype(jnp.float32),
                     reward_ma_init=jnp.ones((), jnp.float32))
    return st, (lc, la)


def chunk_sample_keys(key, n: int):
    """The per-step sampling keys a chunk of n updates will consume,
    plus the advanced carry key. Exposed so parity tests can replay the
    exact batches a chunk draws."""
    carry, samp = jax.random.split(key)
    return carry, jax.random.split(samp, n)


# scan unroll for update chunks: 2 fuses adjacent steps enough to cut
# per-iteration overhead ~30% on CPU without the compile-time blowup of
# higher factors (measured: 6.6 -> 4.6 ms/update at 2, 4.3 at 8)
_SCAN_UNROLL = 2


def update_chunk(cfg: DDPGConfig, st: AgentState,
                 replay: DeviceReplayData, n: int):
    """n critic/actor/target updates as one ``lax.scan``: per-step
    uniform replay sampling, reward-MA advance, and parameter updates
    all stay on device; callers sync once for the (n,) loss arrays.

    The per-step in-scan gather fuses into the update step — measured
    faster than hoisting all n batch gathers out of the scan."""
    carry_key, keys = chunk_sample_keys(st.key, n)
    st = st._replace(key=carry_key)

    def step(carry, k):
        batch = device_replay_sample(replay, k, cfg.batch_size)
        return update_step(cfg, carry, batch)

    return jax.lax.scan(step, st, keys, unroll=min(_SCAN_UNROLL, n))


@partial(jax.jit, static_argnums=(0, 3))
def _update_chunk_jit(cfg, st, replay, n):
    return update_chunk(cfg, st, replay, n)


@partial(jax.jit, static_argnums=(0, 3))
def _population_update_chunk_jit(cfg, sts, replays, n):
    return jax.vmap(lambda s, r: update_chunk(cfg, s, r, n))(sts, replays)


def population_update_chunk_vmap(cfg: DDPGConfig, states: AgentState,
                                 replays: DeviceReplayData, n: int):
    """``jit(vmap(update_chunk))`` over P stacked agent states and
    buffers — the parity REFERENCE for the megabatched path below.

    ``states``/``replays`` are pytrees whose leaves carry a leading
    population axis (see ``tree_stack``)."""
    return _population_update_chunk_jit(cfg, states, replays, n)


# ===========================================================================
# Megabatched population updates (ISSUE 7 tentpole)
# ===========================================================================
#
# The vmap path above turns every per-member op into a (P, ...)-batched op,
# but leaves the autodiff-materialized residual traffic, the tree-form Adam
# (two extra full-tree passes for bias correction), separate Polyak passes,
# and non-donated carries in the program. The megabatched path below writes
# the SAME update step (bit-compatible to ~1e-7) with the population axis
# folded into every GEMM's batch dimension explicitly and the overhead
# structurally removed:
#
#   * merged forwards — the target-actor/target-critic/critic chains run as
#     (P·B)-row batched GEMMs via broadcasted ``jnp.matmul``/``einsum``;
#   * a hand-written backward: only the cotangents DDPG needs are formed
#     (no input-gradient for data tensors; the actor-loss first critic
#     layer is split ``[s, pi] @ W1 = s @ W1[:S] + pi @ W1[S:]`` so the
#     backward computes action-column input grads only);
#   * backward GEMMs in ``einsum`` layout (measured faster than the
#     swapaxes-matmul forms XLA autodiff emits on CPU);
#   * Adam with the bias correction folded into per-step scalars
#     (lr_t = lr·sqrt(1-b2^t)/(1-b1^t), eps_t = eps·sqrt(1-b2^t) — exact
#     rewrite, no mh/vh tree materialization) fused with the Polyak EMA
#     into ONE tree pass;
#   * an optional donated entry point so the carried (P, D) parameter /
#     moment buffers update in place.
#
# On MXU-class backends folding P into the GEMM batch axis is where the
# wall-clock win is expected; it has not been measured on a chip.


def _fused_adam_polyak(params, grads, st, target, lr, tau,
                       b1=0.9, b2=0.999, eps=1e-8):
    """Adam (folded bias correction) + Polyak target EMA in one tree
    pass over stacked (P, ...) leaves. ``st["t"]`` is (P,) int32.

    Exact rewrite of ``adam_step`` + the tau EMA: dividing m by (1-b1^t)
    and v by (1-b2^t) is folded into lr_t/eps_t so no bias-corrected
    tree is ever materialized."""
    t = st["t"] + 1
    tf = t.astype(jnp.float32)
    c1 = 1.0 - b1 ** tf
    c2 = 1.0 - b2 ** tf
    lr_t = lr * jnp.sqrt(c2) / c1        # (P,)
    eps_t = eps * jnp.sqrt(c2)           # (P,)

    def upd(p, m, v, g, tg):
        nd = (1,) * (p.ndim - 1)
        m2 = b1 * m + (1 - b1) * g
        v2 = b2 * v + (1 - b2) * g * g
        p2 = p - lr_t.reshape(-1, *nd) * m2 \
            / (jnp.sqrt(v2) + eps_t.reshape(-1, *nd))
        return (p2, m2, v2, (1 - tau) * tg + tau * p2)

    out = jax.tree.map(upd, params, st["m"], st["v"], grads, target)
    leaves, treedef = jax.tree.flatten(
        out, is_leaf=lambda x: isinstance(x, tuple))
    unf = lambda i: jax.tree.unflatten(treedef, [l[i] for l in leaves])
    return unf(0), {"m": unf(1), "v": unf(2), "t": t}, unf(3)


def _bmm(x, w):
    """(P, B, i) @ (P, i, o): the population axis folded into the GEMM
    batch dimension."""
    return jnp.matmul(x, w)


def _bwd_dw(h, dz):
    """Weight cotangent (P, B, i),(P, B, o) -> (P, i, o)."""
    return jnp.einsum("pbi,pbo->pio", h, dz)


def _bwd_dx(dz, w):
    """Input cotangent (P, B, o),(P, i, o) -> (P, B, i)."""
    return jnp.einsum("pbo,pio->pbi", dz, w)


def _mega_update_step(cfg: DDPGConfig, st: AgentState, batch):
    """One population update step with every GEMM P-megabatched and a
    hand-written backward. Semantics match ``update_step`` member-wise
    (same reward-MA advance, frozen-norm standardization, critic-then-
    actor Adam, Polyak) — the parity tests pin it at <= 1e-5."""
    s, a, r, s2, done = batch            # (P, B, ...) / (P, B)
    S = cfg.state_dim
    bias = lambda l: l["b"][:, None, :]

    batch_mean = jnp.mean(r, axis=1)     # (P,)
    d = cfg.reward_ma_decay
    ma = jnp.where(st.reward_ma_init > 0.0,
                   d * st.reward_ma + (1.0 - d) * batch_mean, batch_mean)
    r = r - ma[:, None]
    inv = 1.0 / jnp.sqrt(st.norm_var + 1e-8)
    s = (s - st.norm_mean[:, None, :]) * inv[:, None, :]
    s2 = (s2 - st.norm_mean[:, None, :]) * inv[:, None, :]

    TA, TC, CR, AC = st.target_actor, st.target_critic, st.critic, st.actor

    # ---- q_target through the target nets (forward only, no grads) ----
    x = jax.nn.relu(_bmm(s2, TA[0]["w"]) + bias(TA[0]))
    x = jax.nn.relu(_bmm(x, TA[1]["w"]) + bias(TA[1]))
    a2 = jax.nn.sigmoid(_bmm(x, TA[2]["w"]) + bias(TA[2]))
    x = jnp.concatenate([s2, a2], -1)
    x = jax.nn.relu(_bmm(x, TC[0]["w"]) + bias(TC[0]))
    x = jax.nn.relu(_bmm(x, TC[1]["w"]) + bias(TC[1]))
    q_next = (_bmm(x, TC[2]["w"]) + bias(TC[2]))[..., 0]
    q_target = r + cfg.gamma * (1.0 - done) * q_next       # (P, B)

    # ---- critic loss: forward + hand backward + fused Adam/Polyak ----
    xc = jnp.concatenate([s, a], -1)
    z1 = _bmm(xc, CR[0]["w"]) + bias(CR[0])
    h1 = jax.nn.relu(z1)
    z2 = _bmm(h1, CR[1]["w"]) + bias(CR[1])
    h2 = jax.nn.relu(z2)
    q = (_bmm(h2, CR[2]["w"]) + bias(CR[2]))[..., 0]
    e = q - q_target
    lc = jnp.mean(e * e, axis=1)                           # (P,)
    dz3 = ((2.0 / e.shape[1]) * e)[..., None]              # d lc / d q
    dW3 = _bwd_dw(h2, dz3)
    db3 = jnp.sum(dz3, axis=1)
    dz2 = _bwd_dx(dz3, CR[2]["w"]) * (z2 > 0)
    dW2 = _bwd_dw(h1, dz2)
    db2 = jnp.sum(dz2, axis=1)
    dz1 = _bwd_dx(dz2, CR[1]["w"]) * (z1 > 0)
    dW1 = _bwd_dw(xc, dz1)
    db1 = jnp.sum(dz1, axis=1)
    gc = [{"w": dW1, "b": db1}, {"w": dW2, "b": db2},
          {"w": dW3, "b": db3}]
    critic, opt_c, t_critic = _fused_adam_polyak(
        CR, gc, st.opt_c, st.target_critic, cfg.critic_lr, cfg.tau)

    # ---- actor loss against the UPDATED critic. The critic's first
    # layer is split [s, pi] @ W1 = s @ W1[:S] + pi @ W1[S:], so the
    # state half is a constant and the backward computes only the
    # action-column input grads (d pi) ----
    w1s = critic[0]["w"][:, :S, :]
    w1a = critic[0]["w"][:, S:, :]
    z1a = _bmm(s, AC[0]["w"]) + bias(AC[0])
    h1a = jax.nn.relu(z1a)
    z2a = _bmm(h1a, AC[1]["w"]) + bias(AC[1])
    h2a = jax.nn.relu(z2a)
    pi = jax.nn.sigmoid(_bmm(h2a, AC[2]["w"]) + bias(AC[2]))
    zq1 = _bmm(s, w1s) + _bmm(pi, w1a) + bias(critic[0])
    hq1 = jax.nn.relu(zq1)
    zq2 = _bmm(hq1, critic[1]["w"]) + bias(critic[1])
    hq2 = jax.nn.relu(zq2)
    qpi = (_bmm(hq2, critic[2]["w"]) + bias(critic[2]))[..., 0]
    la = -jnp.mean(qpi, axis=1)                            # (P,)
    B = qpi.shape[1]
    dz3q = jnp.full_like(hq2[..., :1], -1.0 / B)           # d la / d qpi
    dzq2 = _bwd_dx(dz3q, critic[2]["w"]) * (zq2 > 0)
    dzq1 = _bwd_dx(dzq2, critic[1]["w"]) * (zq1 > 0)
    dpi = _bwd_dx(dzq1, w1a)
    dz3a = dpi * pi * (1.0 - pi)
    dA3 = _bwd_dw(h2a, dz3a)
    db3a = jnp.sum(dz3a, axis=1)
    dz2a = _bwd_dx(dz3a, AC[2]["w"]) * (z2a > 0)
    dA2 = _bwd_dw(h1a, dz2a)
    db2a = jnp.sum(dz2a, axis=1)
    dz1a = _bwd_dx(dz2a, AC[1]["w"]) * (z1a > 0)
    dA1 = _bwd_dw(s, dz1a)
    db1a = jnp.sum(dz1a, axis=1)
    ga = [{"w": dA1, "b": db1a}, {"w": dA2, "b": db2a},
          {"w": dA3, "b": db3a}]
    actor, opt_a, t_actor = _fused_adam_polyak(
        AC, ga, st.opt_a, st.target_actor, cfg.actor_lr, cfg.tau)

    st = st._replace(actor=actor, critic=critic, target_actor=t_actor,
                     target_critic=t_critic, opt_a=opt_a, opt_c=opt_c,
                     reward_ma=ma.astype(jnp.float32),
                     reward_ma_init=jnp.ones_like(st.reward_ma_init))
    return st, (lc, la)


def _mega_chunk(cfg, states, replays, n):
    # per-member key streams replicate chunk_sample_keys / the in-scan
    # device_replay_sample draws of the vmap path exactly
    carry, keys = jax.vmap(lambda k: chunk_sample_keys(k, n))(states.key)
    states = states._replace(key=carry)
    keys = jnp.swapaxes(keys, 0, 1)                       # (n, P, key)

    def step(st, k):
        batch = jax.vmap(device_replay_sample, in_axes=(0, 0, None))(
            replays, k, cfg.batch_size)
        return _mega_update_step(cfg, st, batch)

    st, (lc, la) = jax.lax.scan(step, states, keys,
                                unroll=min(_SCAN_UNROLL, n))
    return st, (jnp.swapaxes(lc, 0, 1), jnp.swapaxes(la, 0, 1))


@partial(jax.jit, static_argnums=(0, 3))
def _population_update_chunk_mega_jit(cfg, states, replays, n):
    return _mega_chunk(cfg, states, replays, n)


@partial(jax.jit, static_argnums=(0, 3), donate_argnums=(1,))
def _population_update_chunk_mega_donate_jit(cfg, states, replays, n):
    return _mega_chunk(cfg, states, replays, n)


def population_update_chunk_megabatched(cfg: DDPGConfig,
                                        states: AgentState,
                                        replays: DeviceReplayData, n: int,
                                        donate: bool = False):
    """The megabatched population chunk: ONE jit execution for the whole
    population's ``n x P`` updates, parameters carried as (P, ...)
    stacked buffers, every GEMM batched over P.

    ``donate=True`` donates the stacked states so the parameter/moment
    buffers update in place — callers must not reuse ``states`` after
    the call (``PopulationSearch`` rebuilds them per dispatch)."""
    fn = _population_update_chunk_mega_donate_jit if donate \
        else _population_update_chunk_mega_jit
    return fn(cfg, states, replays, n)


def population_update_chunk(cfg: DDPGConfig, states: AgentState,
                            replays: DeviceReplayData, n: int,
                            donate: bool = False):
    """Route a population update chunk: the megabatched path by default,
    the ``jit(vmap(update_chunk))`` reference under
    ``GALEN_POP_UPDATE=vmap`` (or for network shapes the hand-written
    step does not cover — anything but the paper's 3-layer trunk).

    Both paths return the same structure: the advanced stacked states
    and per-member ``(P, n)`` critic/actor loss arrays, matching
    member-wise to <= 1e-5 (tests/test_update_floor.py)."""
    if os.environ.get("GALEN_POP_UPDATE") == "vmap" \
            or len(cfg.hidden) != 2:
        return population_update_chunk_vmap(cfg, states, replays, n)
    return population_update_chunk_megabatched(cfg, states, replays, n,
                                               donate=donate)


def tree_stack(trees, shardings=None):
    """Stack a list of identically-shaped pytrees along a new axis 0.

    ``shardings`` (a pytree of ``NamedSharding`` matching the STACKED
    result, e.g. ``distributed.sharding.population_shardings``) commits the
    stack to a device mesh along the member axis. jit follows committed
    input placements, so a subsequent donated population dispatch
    (``population_update_chunk(..., donate=True)`` or the fused epoch
    program) then partitions one member per device and updates the sharded
    buffers in place — the mesh-sharded fleet path costs no extra copies
    over the single-device one."""
    out = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    if shardings is not None:
        out = jax.device_put(out, shardings)
    return out


def tree_index(tree, i: int):
    """Slice member i out of a stacked pytree."""
    return jax.tree.map(lambda x: x[i], tree)


# ===========================================================================
# Compatibility shim
# ===========================================================================

class DDPGAgent:
    """Thin stateful facade over the functional core.

    Keeps the original call sites — ``act`` / ``update`` / ``sigma_at``
    / ``observe_states`` — while all parameters live in ``self.state``
    (an ``AgentState``). ``act`` is the host-numpy actor; the update
    path either takes the original one-host-sample-per-call route
    (``update``) or the fused scan (``update_chunk``).
    """

    def __init__(self, cfg: DDPGConfig, seed: int = 0):
        self.cfg = cfg
        self.state = agent_init(cfg, jax.random.PRNGKey(seed))
        self.norm = RunningNorm(cfg.state_dim)
        self._reward_ma_host = 0.0
        self._reward_ma_init_host = False
        self._ma_dirty = False       # True: state.reward_ma is newer
        self.np_rng = np.random.default_rng(seed)
        self._update = jax.jit(self._update_impl)
        self._actor_host = None            # numpy actor copy for ``act``

    # --- reward-MA facade: after a fused chunk the device value is
    # authoritative; pull it lazily so dispatching a chunk never blocks
    def _sync_ma(self):
        if self._ma_dirty:
            self._reward_ma_host = float(self.state.reward_ma)
            self._reward_ma_init_host = float(self.state.reward_ma_init) > 0
            self._ma_dirty = False

    @property
    def reward_ma(self):
        self._sync_ma()
        return self._reward_ma_host

    @reward_ma.setter
    def reward_ma(self, v):
        self._sync_ma()
        self._reward_ma_host = float(v)

    @property
    def reward_ma_init(self):
        self._sync_ma()
        return self._reward_ma_init_host

    @reward_ma_init.setter
    def reward_ma_init(self, v):
        self._sync_ma()
        self._reward_ma_init_host = bool(v)

    # --- state facade: legacy attribute names read/write the pytree ---
    @property
    def actor(self):
        return self.state.actor

    @actor.setter
    def actor(self, v):
        self.state = self.state._replace(actor=v)
        self._actor_host = None     # ``act`` must see the new weights

    @property
    def critic(self):
        return self.state.critic

    @critic.setter
    def critic(self, v):
        self.state = self.state._replace(critic=v)

    @property
    def target_actor(self):
        return self.state.target_actor

    @target_actor.setter
    def target_actor(self, v):
        self.state = self.state._replace(target_actor=v)

    @property
    def target_critic(self):
        return self.state.target_critic

    @target_critic.setter
    def target_critic(self, v):
        self.state = self.state._replace(target_critic=v)

    @property
    def opt_a(self):
        return self.state.opt_a

    @opt_a.setter
    def opt_a(self, v):
        self.state = self.state._replace(opt_a=v)

    @property
    def opt_c(self):
        return self.state.opt_c

    @opt_c.setter
    def opt_c(self, v):
        self.state = self.state._replace(opt_c=v)

    # ---------------- acting ----------------
    def act(self, state: np.ndarray, sigma: float,
            random: bool = False) -> np.ndarray:
        if random:
            return self.np_rng.uniform(0, 1, self.cfg.action_dim) \
                .astype(np.float32)
        # the actor forward in numpy: a host reference independent of
        # the traced ``actor_forward`` (and of its kernel route)
        x = self.norm.normalize(state.astype(np.float32))
        actor = self._host_actor()
        for i, layer in enumerate(actor):
            x = x @ layer["w"] + layer["b"]
            if i < len(actor) - 1:
                x = np.maximum(x, 0.0)
        mu = (1.0 / (1.0 + np.exp(-x))).astype(np.float32)
        if sigma > 0:
            # truncated normal on [0, 1] around mu (paper Eq. 7)
            for _ in range(16):
                a = self.np_rng.normal(mu, sigma)
                if np.all((a >= 0) & (a <= 1)):
                    return a.astype(np.float32)
            a = np.clip(self.np_rng.normal(mu, sigma), 0, 1)
            return a.astype(np.float32)
        return mu.astype(np.float32)

    def sigma_at(self, episode: int) -> float:
        e = max(0, episode - self.cfg.warmup_episodes)
        return self.cfg.sigma0 * (self.cfg.sigma_decay ** e)

    def _host_actor(self):
        """numpy copy of the actor params, refreshed after updates."""
        if self._actor_host is None:
            self._actor_host = [
                {k: np.asarray(v, np.float32) for k, v in layer.items()}
                for layer in self.state.actor]
        return self._actor_host

    # ---------------- learning ----------------
    def _update_impl(self, actor, critic, t_actor, t_critic, opt_a, opt_c,
                     batch):
        return ddpg_step(self.cfg, actor, critic, t_actor, t_critic,
                         opt_a, opt_c, batch)

    def update(self, replay) -> Tuple[float, float]:
        """Original scalar path: one host replay sample, host reward-MA
        advance and normalization, one jit dispatch. Kept verbatim as
        the parity reference for ``update_chunk``."""
        cfg = self.cfg
        if len(replay) < cfg.batch_size:
            return 0.0, 0.0
        s, a, r, s2, done = replay.sample(cfg.batch_size)
        # normalize rewards in the batch with a moving average (paper)
        batch_mean = float(np.mean(r))
        if not self.reward_ma_init:
            self.reward_ma = batch_mean
            self.reward_ma_init = True
        else:
            d = cfg.reward_ma_decay
            self.reward_ma = d * self.reward_ma + (1 - d) * batch_mean
        r = r - self.reward_ma
        s = self.norm.normalize(s)
        s2 = self.norm.normalize(s2)
        batch = tuple(jnp.asarray(x, jnp.float32) for x in (s, a, r, s2,
                                                            done))
        (actor, critic, t_actor, t_critic, opt_a, opt_c, lc, la) = \
            self._update(self.state.actor, self.state.critic,
                         self.state.target_actor, self.state.target_critic,
                         self.state.opt_a, self.state.opt_c, batch)
        self.state = self.state._replace(
            actor=actor, critic=critic, target_actor=t_actor,
            target_critic=t_critic, opt_a=opt_a, opt_c=opt_c)
        self._actor_host = None
        return float(lc), float(la)

    def update_chunk(self, replay, n: int):
        """Fused path: n updates (sampling included) in one dispatch
        against a ``DeviceReplay``; returns the (n,) loss arrays.

        Does not block: the losses (and the adopted state) are lazy jax
        arrays, so rollout work can overlap the scan."""
        if n <= 0 or len(replay) < self.cfg.batch_size:
            return np.zeros(0, np.float32), np.zeros(0, np.float32)
        st, (lc, la) = _update_chunk_jit(self.cfg, self.state_for_dispatch(),
                                         replay.data, int(n))
        self.adopt_state(st)
        return lc, la

    def state_for_dispatch(self) -> AgentState:
        """Sync host-authoritative stats (running norm, reward-MA) into
        the pytree so a fused dispatch sees the same values the scalar
        path would."""
        st = self.state._replace(
            norm_count=jnp.asarray(self.norm.count, jnp.float32),
            norm_mean=jnp.asarray(self.norm.mean, jnp.float32),
            norm_var=jnp.asarray(self.norm.var, jnp.float32))
        if not self._ma_dirty:      # else the device value is current
            st = st._replace(
                reward_ma=jnp.asarray(self._reward_ma_host, jnp.float32),
                reward_ma_init=jnp.asarray(
                    1.0 if self._reward_ma_init_host else 0.0, jnp.float32))
        return st

    def adopt_state(self, st: AgentState):
        """Take a post-dispatch state as truth; the reward-MA is pulled
        back to the host lazily (first read), so adopting never forces
        a device sync. Invalidates the cached rollout actor."""
        self.state = st
        self._ma_dirty = True
        self._actor_host = None

    def observe_states(self, states: np.ndarray):
        self.norm.update(states)
