"""Flash attention Pallas kernel — GQA, causal / bidirectional / sliding
window. TPU substrate hot spot for prefill_32k (and the reference target
the jnp chunked path in models/layers.py mirrors).

Grid: (batch*q_heads, S/bq, S/bk) with the K axis innermost sequential;
online-softmax running stats (m, l) and the output accumulator live in
VMEM scratch. KV blocks are indexed through the GQA group map
(q head h -> kv head h // group). Window/causal masking is applied
in-block with absolute positions derived from the block indices.

VMEM at defaults (bq=bk=512, D=128): q 256KB + k/v 512KB + acc 256KB
+ stats ≈ 1.1MB. MXU dims: bq×D and bk×D tiles, 128-aligned.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, bq: int, bk: int,
                  seq_len: int):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # [bq, D]
    k = k_ref[0]                       # [bk, D]
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < seq_len
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + jax.lax.dot_general(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(kb == pl.num_programs(2) - 1)
    def _done():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 512, bk: int = 512,
                    interpret: bool = True) -> jnp.ndarray:
    """q [B, H, S, D]; k, v [B, KV, S, D] -> [B, H, S, D]."""
    B, H, S, D = q.shape
    KV = k.shape[1]
    G = H // KV
    bq, bk = min(bq, S), min(bk, S)
    lcm = bq * bk // math.gcd(bq, bk)
    P = math.ceil(S / lcm) * lcm
    if P != S:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, P - S), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, P - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, P - S), (0, 0)))
    Sp = q.shape[2]
    qf = q.reshape(B * H, Sp, D)
    kf = k.reshape(B * KV, Sp, D)
    vf = v.reshape(B * KV, Sp, D)

    def q_map(h, i, j):
        return (h, i, 0)

    def kv_map(h, i, j):
        # flattened q index h = b*H + hh  ->  kv index b*KV + hh // G
        return ((h // H) * KV + (h % H) // G, j, 0)

    scale = 1.0 / math.sqrt(D)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, seq_len=S),
        grid=(B * H, Sp // bq, Sp // bk),
        in_specs=[pl.BlockSpec((1, bq, D), q_map),
                  pl.BlockSpec((1, bk, D), kv_map),
                  pl.BlockSpec((1, bk, D), kv_map)],
        out_specs=pl.BlockSpec((1, bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sp, D)[:, :, :S]
