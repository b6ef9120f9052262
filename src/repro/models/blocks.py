"""Per-kind residual blocks: attn(+MLP/MoE), Mamba-2 SSD, RG-LRU.

Each kind provides ``init_*``, ``apply_*`` (full sequence) and ``decode_*``
(single token + cache). Compression hooks: ``cspec`` — a dict pytree of quant
specs (``{"w_bits","a_bits"}``) and float 0/1 pruning masks; ``None`` means
uncompressed (all hooks compile away).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from repro.models import layers as L


def _qs(cspec, key):
    return None if cspec is None else cspec.get(key)


def _mask(cspec, key):
    return None if cspec is None else cspec.get(key)


# ===========================================================================
# Attention sub-block
# ===========================================================================

def init_attention(key, cfg: ArchConfig, dtype):
    ks = jax.random.split(key, 4)
    H, KV, D, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    p = {
        "wq": L.linear_init(ks[0], d, H * D, dtype, bias=cfg.qkv_bias),
        "wk": L.linear_init(ks[1], d, KV * D, dtype, bias=cfg.qkv_bias),
        "wv": L.linear_init(ks[2], d, KV * D, dtype, bias=cfg.qkv_bias),
        "wo": L.linear_init(ks[3], H * D, d, dtype),
    }
    return p


def _qkv(p, x, cfg: ArchConfig, cspec):
    B, S, _ = x.shape
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qs = _qs(cspec, "qkv")
    q = L.linear(p["wq"], x, qs).reshape(B, S, H, D)
    k = L.linear(p["wk"], x, qs).reshape(B, S, KV, D)
    v = L.linear(p["wv"], x, qs).reshape(B, S, KV, D)
    return q, k, v


def apply_attention(p, x, cfg: ArchConfig, cspec=None, positions=None):
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = _qkv(p, x, cfg, cspec)
    if cfg.position_embedding == "rope":
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    causal = not cfg.is_encoder
    window = cfg.window if cfg.attention == "sliding" else 0
    o = L.attention(q, k, v, causal=causal, window=window,
                    head_mask=_mask(cspec, "head_mask"),
                    scale=cfg.attention_multiplier)
    o = o.reshape(B, S, cfg.num_heads * cfg.head_dim)
    return L.linear(p["wo"], o, _qs(cspec, "o"))


def init_attn_cache(cfg: ArchConfig, batch: int, max_len: int, dtype,
                    cache_bits: int = 16):
    """cache_bits=8 stores K/V as int8 with per-(token, head) scales —
    halves the decode-dominating cache traffic (beyond-paper, §Perf)."""
    W = min(max_len, cfg.window) if cfg.attention == "sliding" else max_len
    KV, D = cfg.num_kv_heads, cfg.head_dim
    if cache_bits <= 8:
        return {
            "k": jnp.zeros((batch, W, KV, D), jnp.int8),
            "v": jnp.zeros((batch, W, KV, D), jnp.int8),
            "k_s": jnp.zeros((batch, W, KV), jnp.float32),
            "v_s": jnp.zeros((batch, W, KV), jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, W, KV, D), dtype),
        "v": jnp.zeros((batch, W, KV, D), dtype),
    }


def _cache_write(cache, name, val, slot):
    """Write [B,1,KV,D] into the cache, quantizing if it is int8.

    NOTE (§Perf B4, REFUTED): a masked-select write (jnp.where on an iota
    mask) was hypothesized to keep length-sharded cache writes local;
    measured 3.6x MORE collective traffic than dynamic-update-slice —
    GSPMD handles the 1-slot DUS better than the broadcast select."""
    buf = cache[name]
    if buf.dtype == jnp.int8:
        scale = jnp.max(jnp.abs(val.astype(jnp.float32)), axis=-1) / 127.0
        scale = jnp.maximum(scale, 1e-8)                     # [B,1,KV]
        q = jnp.clip(jnp.round(val.astype(jnp.float32)
                               / scale[..., None]), -128, 127) \
            .astype(jnp.int8)
        buf = jax.lax.dynamic_update_slice(buf, q, (0, slot, 0, 0))
        sbuf = jax.lax.dynamic_update_slice(
            cache[name + "_s"], scale, (0, slot, 0))
        return {name: buf, name + "_s": sbuf}
    return {name: jax.lax.dynamic_update_slice(
        buf, val.astype(buf.dtype), (0, slot, 0, 0))}


def _cache_read(cache, name, dtype):
    buf = cache[name]
    if buf.dtype == jnp.int8:
        return (buf.astype(jnp.float32)
                * cache[name + "_s"][..., None]).astype(dtype)
    return buf


def decode_attention_block(p, x, cache, pos, cfg: ArchConfig, cspec=None):
    """x: [B,1,d]; pos: scalar current position. Returns (out, cache)."""
    B = x.shape[0]
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = _qkv(p, x, cfg, cspec)
    if cfg.position_embedding == "rope":
        pp = jnp.full((B, 1), pos)
        q = L.rope(q, pp, cfg.rope_theta)
        k = L.rope(k, pp, cfg.rope_theta)
    W = cache["k"].shape[1]
    ring = cfg.attention == "sliding"
    slot = jnp.mod(pos, W) if ring else pos
    new_cache = {}
    new_cache.update(_cache_write(cache, "k", k, slot))
    new_cache.update(_cache_write(cache, "v", v, slot))
    k_cache = _cache_read(new_cache, "k", x.dtype)
    v_cache = _cache_read(new_cache, "v", x.dtype)
    o = L.decode_attention(q, k_cache, v_cache, pos + 1,
                           window=cfg.window if ring else 0, ring=ring,
                           head_mask=_mask(cspec, "head_mask"),
                           scale=cfg.attention_multiplier)
    o = o.reshape(B, 1, H * D)
    out = L.linear(p["wo"], o, _qs(cspec, "o"))
    return out, new_cache


# ===========================================================================
# Dense MLP
# ===========================================================================

def init_mlp(key, cfg: ArchConfig, dtype):
    ks = jax.random.split(key, 3)
    d, ff = cfg.d_model, cfg.d_ff
    gated = cfg.mlp in ("swiglu", "geglu")
    p = {"w_up": L.linear_init(ks[0], d, ff, dtype),
         "w_down": L.linear_init(ks[1], ff, d, dtype)}
    if gated:
        p["w_gate"] = L.linear_init(ks[2], d, ff, dtype)
    return p


def apply_mlp(p, x, cfg: ArchConfig, cspec=None):
    qs_up, qs_down = _qs(cspec, "up"), _qs(cspec, "down")
    ff_mask = _mask(cspec, "ff_mask")
    up = L.linear(p["w_up"], x, qs_up)
    gate = L.linear(p["w_gate"], x, qs_up) if "w_gate" in p else up
    h = L.mlp_act(cfg.mlp, gate, up)
    if ff_mask is not None:
        h = h * ff_mask.astype(h.dtype)
    h = shard(h, "batch", "seq", "ff")
    return L.linear(p["w_down"], h, qs_down)


# ===========================================================================
# MoE (top-k, capacity dispatch; optional Arctic dense residual)
# ===========================================================================

def init_moe(key, cfg: ArchConfig, dtype):
    m = cfg.moe
    ks = jax.random.split(key, 7)
    d, ff, E = cfg.d_model, cfg.d_ff, m.num_experts
    std = 1.0 / math.sqrt(d)
    p = {
        "router": (jax.random.normal(ks[0], (d, E), jnp.float32) * std
                   ).astype(jnp.float32),
        "w_up": (jax.random.normal(ks[1], (E, d, ff), jnp.float32) * std
                 ).astype(dtype),
        "w_gate": (jax.random.normal(ks[2], (E, d, ff), jnp.float32) * std
                   ).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, ff, d), jnp.float32)
                   / math.sqrt(ff)).astype(dtype),
    }
    if m.dense_residual:
        p["dense_w_up"] = L.linear_init(ks[4], d, ff, dtype)["w"]
        p["dense_w_gate"] = L.linear_init(ks[5], d, ff, dtype)["w"]
        p["dense_w_down"] = L.linear_init(ks[6], ff, d, dtype)["w"]
    return p


def moe_dispatch(gates: jnp.ndarray, E: int, K: int, capacity: int):
    """Grouped (shard-local) dispatch. gates: [G, Tg, E] softmax probs ->
    (dispatch_idx [G,E,C], combine [G,Tg,K], slot [G,Tg,K], keep [G,Tg,K]).

    Positions are cumsum'd WITHIN each group; with the group axis sharded
    over ``data`` every gather stays shard-local (no global all-gather of
    the token activations — see DESIGN §4), and the expert einsum's
    resharding is exactly the EP all-to-all."""
    G, Tg, _ = gates.shape
    gate_vals, expert_idx = jax.lax.top_k(gates, K)          # [G, Tg, K]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(expert_idx.reshape(G, Tg * K), E,
                            dtype=jnp.int32)                  # [G, Tg*K, E]
    pos = jnp.cumsum(onehot, axis=1) - onehot
    pos = jnp.sum(pos * onehot, -1)                           # [G, Tg*K]
    keep = pos < capacity
    e_flat = expert_idx.reshape(G, Tg * K)
    pos_c = jnp.where(keep, pos, capacity)                    # overflow slot
    tok = jnp.broadcast_to(jnp.arange(Tg * K) // K, (G, Tg * K))
    dispatch = jnp.full((G, E, capacity + 1), Tg, jnp.int32)
    gi = jnp.broadcast_to(jnp.arange(G)[:, None], (G, Tg * K))
    dispatch = dispatch.at[gi, e_flat, pos_c].set(tok)[:, :, :capacity]
    slot = jnp.where(keep, e_flat * capacity + pos, E * capacity)
    return (dispatch, gate_vals, slot.reshape(G, Tg, K),
            keep.reshape(G, Tg, K))


def _dispatch_groups(T: int, E: int) -> int:
    """Shard-local dispatch group count: the data-axis size, reduced when
    the per-group token count would be tiny (decode)."""
    from repro.distributed.sharding import current_axis_size
    G = current_axis_size("batch")
    while G > 1 and (T % G != 0 or T // G < 4 * E):
        G //= 2
    return max(1, G)


def apply_moe(p, x, cfg: ArchConfig, cspec=None):
    m = cfg.moe
    B, S, d = x.shape
    E, K, ff = m.num_experts, m.top_k, cfg.d_ff
    T = B * S
    G = _dispatch_groups(T, E)
    Tg = T // G
    xt = x.reshape(G, Tg, d)
    xt = shard(xt, "batch", None, None)
    qs_up, qs_down = _qs(cspec, "up"), _qs(cspec, "down")
    ff_mask = _mask(cspec, "ff_mask")

    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32), p["router"])
    gates = jax.nn.softmax(logits, -1)
    if Tg * E <= 4096:
        cap = Tg           # small token counts (decode/smoke): no dropping
    else:
        cap = int(math.ceil(K * Tg / E * m.capacity_factor))
        cap = max(4, -(-cap // 4) * 4)
    dispatch, gate_vals, slot, keep = moe_dispatch(gates, E, K, cap)

    xt_pad = jnp.concatenate([xt, jnp.zeros((G, 1, d), xt.dtype)], 1)
    idx = dispatch.reshape(G, E * cap)
    xe = jnp.take_along_axis(xt_pad, idx[..., None],
                             axis=1).reshape(G, E, cap, d)
    xe = shard(xe, "batch", "experts", None, None)

    dt = x.dtype
    w_up = L.getw(p, "w_up", dt)
    w_gate = L.getw(p, "w_gate", dt)
    w_down = L.getw(p, "w_down", dt)
    if qs_up is not None:
        xe = L.fq_act(xe, qs_up["a_bits"])
        w_up = L.fq_weight(w_up, qs_up["w_bits"], xe.dtype)
        w_gate = L.fq_weight(w_gate, qs_up["w_bits"], xe.dtype)
    up = jnp.einsum("gecd,edf->gecf", xe, w_up.astype(xe.dtype))
    gate = jnp.einsum("gecd,edf->gecf", xe, w_gate.astype(xe.dtype))
    h = L.mlp_act("swiglu" if cfg.mlp == "swiglu" else "geglu", gate, up)
    if ff_mask is not None:
        h = h * ff_mask[None, None, None].astype(h.dtype)
    h = shard(h, "batch", "experts", None, "ff")
    if qs_down is not None:
        h = L.fq_act(h, qs_down["a_bits"])
        w_down = L.fq_weight(w_down, qs_down["w_bits"], h.dtype)
    ye = jnp.einsum("gecf,efd->gecd", h, w_down.astype(h.dtype))
    if m.combine == "reduce_scatter":
        # §Perf A2: the down-proj contracts over the model-sharded ff dim;
        # constraining ye's d axis onto the model axis turns the partial-sum
        # combine into a REDUCE-SCATTER of [G,E,cap,d] (vs an all-reduce of
        # the full 2.5x-inflated capacity buffer). The token gather below is
        # d-local; only the final [G,Tg,d] output is all-gathered.
        ye = shard(ye, "batch", "experts", None, "ff")

    ye_flat = ye.reshape(G, E * cap, d)
    ye_flat = jnp.concatenate([ye_flat, jnp.zeros((G, 1, d), ye.dtype)], 1)
    per_tk = jnp.take_along_axis(
        ye_flat, slot.reshape(G, Tg * K)[..., None],
        axis=1).reshape(G, Tg, K, d)
    w = jnp.where(keep, gate_vals, 0.0).astype(per_tk.dtype)
    out = jnp.sum(per_tk * w[..., None], axis=2)
    if m.combine == "reduce_scatter":
        out = shard(out, "batch", None, "ff")      # still d-sharded
    out = out.reshape(B, S, d)

    if m.dense_residual:
        dspec = None
        if cspec is not None:
            dspec = {"up": cspec.get("dense_up"), "down": cspec.get("dense_down"),
                     "ff_mask": cspec.get("dense_ff_mask")}
        def as_linear(v):
            return v if isinstance(v, dict) else {"w": v}
        dense = apply_mlp({"w_up": as_linear(p["dense_w_up"]),
                           "w_gate": as_linear(p["dense_w_gate"]),
                           "w_down": as_linear(p["dense_w_down"])},
                          x, cfg, dspec)
        out = out + dense
    return out


# ===========================================================================
# Mamba-2 (SSD) block
# ===========================================================================

def ssm_dims(cfg: ArchConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    nheads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.d_state
    return d_inner, nheads, conv_dim


def init_ssm(key, cfg: ArchConfig, dtype):
    s = cfg.ssm
    d_inner, nheads, conv_dim = ssm_dims(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    d_proj = 2 * d_inner + 2 * s.d_state + nheads  # z, x, B, C, dt
    p = {
        "in_proj": L.linear_init(ks[0], d, d_proj, dtype)["w"],
        "out_proj": L.linear_init(ks[1], d_inner, d, dtype)["w"],
        "conv_w": (jax.random.normal(ks[2], (s.conv_width, conv_dim),
                                     jnp.float32) / s.conv_width).astype(dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nheads).astype(jnp.float32)),
        "D": jnp.ones((nheads,), jnp.float32),
        "dt_bias": jnp.zeros((nheads,), jnp.float32),
        "norm_scale": jnp.ones((d_inner,), dtype),
    }
    if s.conv_bias:
        p["conv_b"] = jnp.zeros((conv_dim,), dtype)
    return p


def _segsum(a):
    """a: [..., l] log-decays -> [..., l, l] lower-tri cumulative sums."""
    l = a.shape[-1]
    cs = jnp.cumsum(a, -1)
    d = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool))
    return jnp.where(mask, d, -jnp.inf)


def ssd_chunked(xh, dA, Bm, Cm, chunk: int, init_state=None):
    """Chunked SSD scan (Mamba-2, arXiv:2405.21060 listing 1).

    xh: [b,s,h,p] (dt-scaled inputs); dA: [b,s,h] log decay per step;
    Bm, Cm: [b,s,n] (ngroups=1). Returns y [b,s,h,p], final state [b,h,p,n].
    """
    b, s, h, pdim = xh.shape
    n = Bm.shape[-1]
    chunk = min(chunk, s)          # a shorter sequence is one chunk, unpadded
    pad = (-s) % chunk
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dA = jnp.pad(dA, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    c = sp // chunk
    X = xh.reshape(b, c, chunk, h, pdim)
    A = dA.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)      # [b,h,c,l]
    Bc = Bm.reshape(b, c, chunk, n)
    Cc = Cm.reshape(b, c, chunk, n)

    A_cum = jnp.cumsum(A, -1)                                  # [b,h,c,l]
    Lmat = jnp.exp(_segsum(A))                                 # [b,h,c,l,l]
    # intra-chunk (quadratic within chunk)
    Y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", Cc, Bc, Lmat, X)
    # chunk states
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)            # [b,h,c,l]
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bc, decay_states, X)
    # inter-chunk recurrence
    chunk_decay = jnp.exp(A_cum[..., -1])                      # [b,h,c]
    s0 = (jnp.zeros((b, h, pdim, n), X.dtype)
          if init_state is None else init_state)

    def step(prev, inp):
        st, dec = inp                                          # [b,h,p,n],[b,h]
        out = prev                                             # state BEFORE chunk
        new = st + dec[..., None, None] * prev
        return new, out

    sts = states.transpose(1, 0, 2, 3, 4)                      # [c,b,h,p,n]
    dcs = chunk_decay.transpose(2, 0, 1)                       # [c,b,h]
    final, prev_states = jax.lax.scan(step, s0, (sts, dcs))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)         # [b,c,h,p,n]
    state_decay = jnp.exp(A_cum)                               # [b,h,c,l]
    Y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", Cc, prev_states, state_decay)
    Y = (Y_diag + Y_off).reshape(b, sp, h, pdim)[:, :s]
    return Y, final


def _ssm_inner(p, x, cfg, cspec, conv_state, ssm_state, *, decode=False):
    """The Mamba-2 mixer (arXiv:2405.21060; HF ``Mamba2Mixer``), shared by
    the full-sequence and decode paths. x: [B,S,d].

    in_proj -> [z, xBC, dt]; depthwise causal conv (+ bias) over xBC,
    then SiLU; split into x, B, C; dt = softplus(dt + dt_bias),
    A = -exp(A_log); SSD (chunked, or one recurrence step when decoding)
    + D·x; pruned heads zeroed; gated RMSNorm norm(y · silu(z)) over
    d_inner; out_proj."""
    s = cfg.ssm
    d_inner, nheads, conv_dim = ssm_dims(cfg)
    qs_in, qs_out = _qs(cspec, "in"), _qs(cspec, "out")
    head_mask = _mask(cspec, "head_mask")

    w_in = L.getw(p, "in_proj", x.dtype)
    xin = x
    if qs_in is not None:
        xin = L.fq_act(xin, qs_in["a_bits"])
        w_in = L.fq_weight(w_in, qs_in["w_bits"], x.dtype)
    proj = jnp.einsum("bsd,dk->bsk", xin, w_in.astype(x.dtype))
    z, xbc, dt = jnp.split(proj, [d_inner, d_inner + conv_dim], axis=-1)
    y_conv, new_conv = L.causal_conv1d(xbc, p["conv_w"], conv_state,
                                       bias=p.get("conv_b"))
    y_conv = jax.nn.silu(y_conv)
    xs, Bm, Cm = jnp.split(y_conv, [d_inner, d_inner + s.d_state], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"][None, None])           # [B,S,h]
    a = -jnp.exp(p["A_log"])                                   # [h]
    dA = dt * a[None, None]
    xh = xs.reshape(*xs.shape[:2], nheads, s.head_dim)
    xh_dt = xh.astype(jnp.float32) * dt[..., None]

    if decode:
        # single step: state' = exp(dA) state + B ⊗ x_dt ; y = C·state'
        dec = jnp.exp(dA[:, 0])                                # [B,h]
        upd = jnp.einsum("bn,bhp->bhpn", Bm[:, 0].astype(jnp.float32),
                         xh_dt[:, 0])
        new_state = dec[..., None, None] * ssm_state + upd
        y = jnp.einsum("bn,bhpn->bhp", Cm[:, 0].astype(jnp.float32),
                       new_state)[:, None]
    else:
        with jax.named_scope("ssd"):
            y, new_state = ssd_chunked(xh_dt, dA, Bm.astype(jnp.float32),
                                       Cm.astype(jnp.float32), s.chunk_size,
                                       ssm_state)
    y = y + p["D"][None, None, :, None] * xh.astype(jnp.float32)
    if head_mask is not None:
        y = y * head_mask[None, None, :, None]
    y = y.reshape(*x.shape[:2], d_inner).astype(x.dtype)
    # gated RMSNorm (mamba2)
    y = L.apply_norm("rmsnorm", {"scale": p["norm_scale"]},
                     y * jax.nn.silu(z), cfg.norm_eps)
    w_out = L.getw(p, "out_proj", y.dtype)
    if qs_out is not None:
        y = L.fq_act(y, qs_out["a_bits"])
        w_out = L.fq_weight(w_out, qs_out["w_bits"], y.dtype)
    out = jnp.einsum("bsd,dk->bsk", y, w_out.astype(y.dtype))
    return out, new_conv, new_state


def apply_ssm(p, x, cfg: ArchConfig, cspec=None):
    # the scope tags the mixer's ops in a profiler trace (metadata only)
    with jax.named_scope("mamba"):
        out, _, _ = _ssm_inner(p, x, cfg, cspec, None, None)
    return out


def init_ssm_cache(cfg: ArchConfig, batch: int, dtype):
    s = cfg.ssm
    d_inner, nheads, conv_dim = ssm_dims(cfg)
    return {
        "conv": jnp.zeros((batch, s.conv_width - 1, conv_dim), dtype),
        "state": jnp.zeros((batch, nheads, s.head_dim, s.d_state),
                           jnp.float32),
    }


def decode_ssm(p, x, cache, pos, cfg: ArchConfig, cspec=None):
    with jax.named_scope("mamba"):
        out, conv, state = _ssm_inner(p, x, cfg, cspec, cache["conv"],
                                      cache["state"], decode=True)
    return out, {"conv": conv, "state": state}


# ===========================================================================
# RG-LRU (Griffin / RecurrentGemma) recurrent block
# ===========================================================================

_LRU_C = 8.0


def init_rglru(key, cfg: ArchConfig, dtype):
    d, w = cfg.d_model, cfg.lru_width
    ks = jax.random.split(key, 5)
    p = {
        "w_x": L.linear_init(ks[0], d, w, dtype)["w"],
        "w_y": L.linear_init(ks[1], d, w, dtype)["w"],
        "w_out": L.linear_init(ks[2], w, d, dtype)["w"],
        "conv_w": (jax.random.normal(ks[3], (4, w), jnp.float32) / 4.0
                   ).astype(dtype),
        # per-channel (diagonal) gates — see DESIGN.md (Griffin uses
        # block-diagonal heads; diagonal is the width-1 special case)
        "w_a": jnp.zeros((w,), jnp.float32),
        "b_a": jnp.zeros((w,), jnp.float32),
        "w_i": jnp.zeros((w,), jnp.float32),
        "b_i": jnp.zeros((w,), jnp.float32),
        # Λ init so a^c ≈ U(0.9, 0.999) at r=1 (Griffin App. A)
        "a_param": jnp.log(jnp.expm1(
            -jnp.log(jnp.linspace(0.9, 0.999, w)) / _LRU_C)).astype(jnp.float32),
    }
    return p


def _rglru_gates(p, u):
    uf = u.astype(jnp.float32)
    r = jax.nn.sigmoid(uf * p["w_a"] + p["b_a"])
    i = jax.nn.sigmoid(uf * p["w_i"] + p["b_i"])
    log_a = -_LRU_C * jax.nn.softplus(p["a_param"]) * r
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * uf)
    return a, b


def _lru_scan(a, b, h0=None):
    """h_t = a_t h_{t-1} + b_t via associative scan over axis 1."""
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0)
    def comb(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2
    _, h = jax.lax.associative_scan(comb, (a, b), axis=1)
    return h


def apply_rglru(p, x, cfg: ArchConfig, cspec=None):
    qs_in, qs_out = _qs(cspec, "in"), _qs(cspec, "out")
    wmask = _mask(cspec, "width_mask")
    w_x = L.getw(p, "w_x", x.dtype)
    w_y = L.getw(p, "w_y", x.dtype)
    w_out = L.getw(p, "w_out", x.dtype)
    xin = x
    if qs_in is not None:
        xin = L.fq_act(xin, qs_in["a_bits"])
        w_x = L.fq_weight(w_x, qs_in["w_bits"], x.dtype)
        w_y = L.fq_weight(w_y, qs_in["w_bits"], x.dtype)
    y = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", xin, w_y.astype(x.dtype)))
    u = jnp.einsum("bsd,dw->bsw", xin, w_x.astype(x.dtype))
    u, _ = L.causal_conv1d(u, p["conv_w"])
    a, b = _rglru_gates(p, u)
    h = _lru_scan(a, b).astype(x.dtype)
    g = h * y
    if wmask is not None:
        g = g * wmask.astype(g.dtype)
    g = shard(g, "batch", "seq", "ff")
    if qs_out is not None:
        g = L.fq_act(g, qs_out["a_bits"])
        w_out = L.fq_weight(w_out, qs_out["w_bits"], g.dtype)
    return jnp.einsum("bsw,wd->bsd", g, w_out.astype(g.dtype))


def init_rglru_cache(cfg: ArchConfig, batch: int, dtype):
    return {
        "state": jnp.zeros((batch, cfg.lru_width), jnp.float32),
        "conv": jnp.zeros((batch, 3, cfg.lru_width), dtype),
    }


def decode_rglru(p, x, cache, pos, cfg: ArchConfig, cspec=None):
    qs_in, qs_out = _qs(cspec, "in"), _qs(cspec, "out")
    wmask = _mask(cspec, "width_mask")
    w_x = L.getw(p, "w_x", x.dtype)
    w_y = L.getw(p, "w_y", x.dtype)
    w_out = L.getw(p, "w_out", x.dtype)
    xin = x
    if qs_in is not None:
        xin = L.fq_act(xin, qs_in["a_bits"])
        w_x = L.fq_weight(w_x, qs_in["w_bits"], x.dtype)
        w_y = L.fq_weight(w_y, qs_in["w_bits"], x.dtype)
    y = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", xin, w_y.astype(x.dtype)))
    u = jnp.einsum("bsd,dw->bsw", xin, w_x.astype(x.dtype))
    u, conv = L.causal_conv1d(u, p["conv_w"], cache["conv"])
    a, b = _rglru_gates(p, u)
    h = a[:, 0] * cache["state"] + b[:, 0]
    g = (h[:, None].astype(x.dtype)) * y
    if wmask is not None:
        g = g * wmask.astype(g.dtype)
    if qs_out is not None:
        g = L.fq_act(g, qs_out["a_bits"])
        w_out = L.fq_weight(w_out, qs_out["w_bits"], g.dtype)
    out = jnp.einsum("bsw,wd->bsd", g, w_out.astype(g.dtype))
    return out, {"state": h, "conv": conv}
