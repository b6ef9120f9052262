"""Plain reference of the search agent's learning: DDPG (Lillicrap et
al. 2015) as the Galen paper configures it. Actor and critic are
3-layer MLPs (ReLU trunks; a sigmoid actor head, a linear critic head
on the concatenated state and action), initialised uniform in
+-1/sqrt(fan-in) with the last layer in +-3e-3 and zero biases, from
``PRNGKey(seed)`` split into actor, critic and sampling keys. Each
update step:

1. the reward moving average takes the batch mean (the first step) or
   decays towards it, and is subtracted from the batch's rewards;
2. states are standardised with the running statistics (mean, variance
   + 1e-8), which advance once per episode batch over its states by the
   parallel-variance formula, from count 1e-4, mean 0, variance 1;
3. the critic descends the squared TD error against the target
   networks (gamma, done masking), then the actor ascends the critic's
   value of its own action, each by Adam (b1 0.9, b2 0.999, eps 1e-8,
   bias-corrected);
4. both target networks move by Polyak averaging with ``tau``.

Minibatches are uniform draws of replay rows: a chunk of n steps splits
the carried key into (next carry, sample key), the sample key into n
step keys, and each step draws ``randint(key, (batch,), 0, size)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.numerics import Numerics


def _mlp_init(key, dims, final_scale=3e-3):
    layers = []
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        key, k = jax.random.split(key)
        lim = final_scale if i == len(dims) - 2 else 1.0 / np.sqrt(a)
        layers.append({"w": jax.random.uniform(k, (a, b), jnp.float32,
                                               -lim, lim),
                       "b": jnp.zeros((b,), jnp.float32)})
    return layers


def init(seed: int, state_dim: int, action_dim: int, hidden) -> dict:
    k1, k2, key = jax.random.split(jax.random.PRNGKey(seed), 3)
    actor = _mlp_init(k1, (state_dim, *hidden, action_dim))
    critic = _mlp_init(k2, (state_dim + action_dim, *hidden, 1))
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    return {"actor": actor, "critic": critic,
            "target_actor": actor, "target_critic": critic,
            "m_a": zeros(actor), "v_a": zeros(actor),
            "m_c": zeros(critic), "v_c": zeros(critic),
            "t": jnp.zeros((), jnp.int32),
            "norm_count": jnp.asarray(1e-4, jnp.float32),
            "norm_mean": jnp.zeros((state_dim,), jnp.float32),
            "norm_var": jnp.ones((state_dim,), jnp.float32),
            "ma": jnp.zeros((), jnp.float32),
            "ma_init": jnp.zeros((), jnp.float32),
            "key": key}


def observe(st: dict, states) -> dict:
    x = jnp.asarray(states, jnp.float32)
    n = jnp.asarray(x.shape[0], jnp.float32)
    bm, bv = x.mean(0), x.var(0)
    delta = bm - st["norm_mean"]
    tot = st["norm_count"] + n
    var = (st["norm_var"] * st["norm_count"] + bv * n
           + delta ** 2 * st["norm_count"] * n / tot) / tot
    return {**st, "norm_count": tot,
            "norm_mean": st["norm_mean"] + delta * n / tot, "norm_var": var}


def _mlp(num, layers, x, sigmoid):
    for i, l in enumerate(layers):
        x = num.mm("bi,io->bo", x, l["w"]) + num.cast(l["b"])
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return jax.nn.sigmoid(x) if sigmoid else x


def _critic(num, layers, s, a):
    return _mlp(num, layers, jnp.concatenate([s, a], -1), False)[..., 0]


def _adam(num, p, g, m, v, t, lr):
    b1, b2, eps = 0.9, 0.999, 1e-8
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    c1 = 1.0 - b1 ** t.astype(jnp.float32)
    c2 = 1.0 - b2 ** t.astype(jnp.float32)
    p = jax.tree.map(
        lambda p, m, v: num.cast(p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps)),
        p, m, v)
    return p, m, v


def update(cfg: dict, st: dict, batch, prec: str = "f32"):
    """One update step on an explicit minibatch; returns (state, the
    critic's and the actor's gradients)."""
    num = Numerics(prec)
    s, a, r, s2, done = (jnp.asarray(x, jnp.float32) for x in batch)
    mean_r = jnp.mean(r)
    d = cfg["reward_ma_decay"]
    ma = jnp.where(st["ma_init"] > 0, d * st["ma"] + (1 - d) * mean_r, mean_r)
    r = r - ma
    inv = 1.0 / jnp.sqrt(st["norm_var"] + 1e-8)
    s = num.cast((s - st["norm_mean"]) * inv)
    s2 = num.cast((s2 - st["norm_mean"]) * inv)
    a, r, done = num.cast(a), num.cast(r), num.cast(done)
    a2 = _mlp(num, st["target_actor"], s2, True)
    q_t = jax.lax.stop_gradient(
        r + cfg["gamma"] * (1.0 - done) * _critic(num, st["target_critic"],
                                                 s2, a2))

    def critic_loss(c):
        return jnp.mean((_critic(num, c, s, a) - q_t) ** 2)

    t = st["t"] + 1
    gc = jax.grad(critic_loss)(st["critic"])
    critic, m_c, v_c = _adam(num, st["critic"], gc, st["m_c"], st["v_c"], t,
                             cfg["critic_lr"])

    def actor_loss(p):
        return -jnp.mean(_critic(num, critic, s, _mlp(num, p, s, True)))

    ga = jax.grad(actor_loss)(st["actor"])
    actor, m_a, v_a = _adam(num, st["actor"], ga, st["m_a"], st["v_a"], t,
                            cfg["actor_lr"])
    tau = cfg["tau"]
    soft = lambda tg, on: jax.tree.map(
        lambda x, y: num.cast((1 - tau) * x + tau * y), tg, on)
    st = {**st, "actor": actor, "critic": critic, "m_a": m_a, "v_a": v_a,
          "m_c": m_c, "v_c": v_c, "t": t,
          "target_actor": soft(st["target_actor"], actor),
          "target_critic": soft(st["target_critic"], critic),
          "ma": ma.astype(jnp.float32),
          "ma_init": jnp.ones((), jnp.float32)}
    return st, (gc, ga)


def chunk(cfg: dict, st: dict, ring: dict, n: int, prec: str = "f32"):
    """n update steps drawing from ``ring`` (arrays and ``size``); the
    first step's gradients come back for the leaf-selection rule."""
    carry, samp = jax.random.split(st["key"])
    keys = jax.random.split(samp, n)
    st = {**st, "key": carry}
    size = jnp.maximum(ring["size"], 1)

    def step(st, k):
        idx = jax.random.randint(k, (cfg["batch_size"],), 0, size)
        batch = tuple(ring[f][idx] for f in ("states", "actions", "rewards",
                                             "next_states", "dones"))
        return update(cfg, st, batch, prec)

    st, grads = step(st, keys[0])
    st, _ = jax.lax.scan(lambda c, k: (step(c, k)[0], None), st, keys[1:])
    return st, grads
