"""Layer primitives shared by every assigned architecture.

Every matmul-bearing primitive takes an optional quant spec ``qs`` —
``{"w_bits": i32[], "a_bits": i32[]}`` — and optional structured-pruning
masks, so a Galen compression policy can flow through the whole model
(including ``lax.scan``-stacked layer stacks, where specs are stacked on a
leading layer axis). With ``qs=None``/``mask=None`` the hooks vanish
statically — the uncompressed model pays zero overhead.

Weight layout convention: ``[in, out]`` (biases ``[out]``).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quantization import (fake_quant_act, fake_quant_rows,
                                     fake_quant_weight)
from repro.distributed.sharding import shard

# Short aliases used throughout the model code.
fq_act = fake_quant_act
fq_weight = fake_quant_weight
fq_rows = fake_quant_rows


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Linear (+ fake quant + masks)
# ---------------------------------------------------------------------------

def linear_init(key, d_in: int, d_out: int, dtype, bias: bool = False,
                scale: Optional[float] = None):
    std = scale if scale is not None else 1.0 / math.sqrt(d_in)
    w = (jax.random.normal(key, (d_in, d_out), jnp.float32) * std).astype(dtype)
    if bias:
        return {"w": w, "b": jnp.zeros((d_out,), dtype)}
    return {"w": w}


def apply_quant(x: jnp.ndarray, w: jnp.ndarray, qs: Optional[dict]):
    """Apply activation/weight fake quantization per the spec."""
    if qs is not None:
        x = fake_quant_act(x, qs["a_bits"])
        w = fake_quant_weight(w, qs["w_bits"], x.dtype)
    return x, w


def materialize_weight(p, dtype):
    """Resolve a weight container (see core/deploy.py) to a dense array.
    Deployed int8/int4 storage dequantizes on the fly — HBM reads the
    integer container; the convert fuses into the consuming matmul."""
    if not isinstance(p, dict):
        return p
    if "w" in p:
        return p["w"]
    if "w_q" in p:
        return (p["w_q"].astype(dtype) * p["w_scale"].astype(dtype))
    if "w_p" in p:
        from repro.core.deploy import unpack_int4_weight
        wq = unpack_int4_weight(p["w_p"])
        return wq.astype(dtype) * p["w_scale"].astype(dtype)
    raise KeyError(f"no weight in container: {list(p)}")


def getw(container, name, dtype):
    """Fetch a possibly-deploy-quantized raw weight (MoE/SSM/RG-LRU/embed)."""
    v = container[name]
    if isinstance(v, dict):
        return materialize_weight(v, dtype)
    return v


def linear(p: dict, x: jnp.ndarray, qs: Optional[dict] = None,
           out_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    w = materialize_weight(p, x.dtype)
    x, w = apply_quant(x, w, qs)
    y = jnp.einsum("...i,io->...o", x, w.astype(x.dtype))
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    if out_mask is not None:
        y = y * out_mask.astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(kind: str, d: int, dtype):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    if kind == "nonparametric_ln":
        return {}
    raise ValueError(kind)


def apply_norm(kind: str, p: dict, x: jnp.ndarray, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (xf * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    xf = (xf - mu) * jax.lax.rsqrt(var + eps)
    if kind == "layernorm":
        xf = xf * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return xf.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., S, H, D]; positions: [..., S] (broadcastable)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs        # [..., S, half]
    ang = ang[..., None, :]                                       # [..., S, 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention — chunked (flash-style) jnp path, compiles on any backend with
# O(S·W) live memory; the Pallas kernel (repro/kernels/flash_attention.py) is
# the TPU fast path and is numerically checked against this implementation.
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _attn_scores_mask(qpos, kpos, causal: bool, window: int):
    """qpos [Q], kpos [K] -> bool mask [Q, K] (True = attend)."""
    qp = qpos[:, None]
    kp = kpos[None, :]
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= kp <= qp
    if window > 0:
        m &= kp > qp - window
    m &= kp >= 0
    return m


def attention(q, k, v, *, causal: bool, window: int = 0,
              q_chunk: int = 512, k_chunk: int = 1024,
              head_mask: Optional[jnp.ndarray] = None,
              scale: Optional[float] = None) -> jnp.ndarray:
    """GQA attention. q: [B,S,H,D]; k,v: [B,S,KV,D]. window=0 -> unlimited;
    ``scale`` None -> 1/sqrt(D).

    For S <= q_chunk falls back to one dense block; otherwise scans q-chunks
    (outer) and k-chunks (inner, online softmax) so the live score tensor is
    [Cq, Ck] per head group — the jnp equivalent of flash attention.
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    qq = q.reshape(B, S, KV, G, D)
    positions = jnp.arange(S)

    if S <= max(q_chunk, 512):  # small: single dense block
        s = jnp.einsum("bqkgd,blkd->bkgql", qq, k).astype(jnp.float32) * scale
        mask = _attn_scores_mask(positions, positions, causal, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, -1)
        o = jnp.einsum("bkgql,blkd->bqkgd", p.astype(v.dtype), v)
        o = o.reshape(B, S, H, D)
        if head_mask is not None:
            o = o * head_mask[None, None, :, None].astype(o.dtype)
        return o

    n_q = -(-S // q_chunk)
    n_k = -(-S // k_chunk)
    S_pad_q, S_pad_k = n_q * q_chunk, n_k * k_chunk

    def pad_s(x, to):
        return jnp.pad(x, ((0, 0), (0, to - S)) + ((0, 0),) * (x.ndim - 2))

    qq_p = pad_s(qq, S_pad_q)
    k_p, v_p = pad_s(k, S_pad_k), pad_s(v, S_pad_k)
    qpos = jnp.pad(positions, (0, S_pad_q - S), constant_values=S)
    kpos = jnp.pad(positions, (0, S_pad_k - S), constant_values=-1)

    qc = qq_p.reshape(B, n_q, q_chunk, KV, G, D).transpose(1, 0, 3, 4, 2, 5)
    kc = k_p.reshape(B, n_k, k_chunk, KV, D).transpose(1, 0, 3, 2, 4)
    vc = v_p.reshape(B, n_k, k_chunk, KV, D).transpose(1, 0, 3, 2, 4)
    qpc = qpos.reshape(n_q, q_chunk)
    kpc = kpos.reshape(n_k, k_chunk)

    def q_block(args):
        qi, qp = args  # qi: [B,KV,G,Cq,D], qp: [Cq]

        def k_step(carry, kargs):
            m_run, l_run, acc = carry
            ki, vi, kp = kargs  # [B,KV,Ck,D], [Ck]
            s = jnp.einsum("bkgqd,bkld->bkgql", qi, ki).astype(jnp.float32) * scale
            mask = _attn_scores_mask(qp, kp, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, -1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l_run * alpha + jnp.sum(p, -1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgql,bkld->bkgqd", p.astype(vi.dtype), vi).astype(jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(k_step, (m0, l0, a0), (kc, vc, kpc))
        return acc / jnp.maximum(l[..., None], 1e-30)

    out = jax.lax.map(q_block, (qc, qpc))                 # [n_q,B,KV,G,Cq,D]
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, S_pad_q, H, D)[:, :S]
    out = out.astype(v.dtype)
    if head_mask is not None:
        out = out * head_mask[None, None, :, None].astype(out.dtype)
    return out


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     window: int = 0, ring: bool = False,
                     head_mask: Optional[jnp.ndarray] = None,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """Single-token attention against a cache.

    q: [B,1,H,D]; caches: [B,W,KV,D]; cache_len: current length (scalar).
    ``ring=True`` means the cache is a ring buffer of size W (sliding
    window) — all valid slots are attended, positions already rotated.
    """
    B, _, H, D = q.shape
    W, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qq = q.reshape(B, KV, G, D)
    s = jnp.einsum("bkgd,blkd->bkgl", qq, k_cache).astype(jnp.float32)
    s = s / math.sqrt(D) if scale is None else s * scale
    slot = jnp.arange(W)
    valid = slot < cache_len if not ring else slot < jnp.minimum(cache_len, W)
    if window > 0 and not ring:
        valid &= slot > cache_len - 1 - window
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, -1)
    o = jnp.einsum("bkgl,blkd->bkgd", p.astype(v_cache.dtype), v_cache)
    o = o.reshape(B, 1, H, D)
    if head_mask is not None:
        o = o * head_mask[None, None, :, None].astype(o.dtype)
    return o


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def mlp_act(kind: str, gate: jnp.ndarray, up: Optional[jnp.ndarray]):
    if kind == "swiglu":
        return jax.nn.silu(gate) * up
    if kind == "geglu":
        return jax.nn.gelu(gate) * up
    if kind == "gelu":
        return jax.nn.gelu(gate)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Depthwise causal conv1d (SSM / RG-LRU front conv)
# ---------------------------------------------------------------------------

def causal_conv1d(x: jnp.ndarray, w: jnp.ndarray,
                  state: Optional[jnp.ndarray] = None,
                  bias: Optional[jnp.ndarray] = None):
    """x: [B,S,C]; w: [K,C] depthwise; bias: [C] or None. Returns y
    ([B,S,C]) and new state ([B,K-1,C]) holding the last K-1 inputs for
    streaming decode."""
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xs = jnp.concatenate([state, x], axis=1)            # [B, S+K-1, C]
    y = sum(xs[:, i:i + x.shape[1]] * w[i][None, None] for i in range(K))
    if bias is not None:
        y = y + bias[None, None]
    new_state = xs[:, -(K - 1):] if K > 1 else state
    return y.astype(x.dtype), new_state
