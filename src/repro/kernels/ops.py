"""Jit'd public wrappers around the Pallas kernels.

Each op pads/reshapes to kernel-legal shapes, dispatches to the kernel
(``interpret=True`` on CPU — the dev/test path; on TPU backends the same
call compiles to Mosaic), and restores the caller's layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import fake_quant as _fq
from repro.kernels import flash_attention as _fa
from repro.kernels import mlp_fused as _mlp
from repro.kernels import quant_matmul as _qm
from repro.kernels import ref as _ref
from repro.kernels import rglru_scan as _rg
from repro.kernels import ssd_scan as _ssd


_LANE = 128      # f32 lane multiple (last axis)
_SUBLANE = 8     # f32 sublane multiple (second-to-last axis)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("w_bits", "out_dtype"))
def quantized_matmul(x: jnp.ndarray, w: jnp.ndarray, w_bits: int = 8,
                     out_dtype=jnp.float32) -> jnp.ndarray:
    """f32/bf16 x [M,K] @ w [K,N] through the int8/int4 quantized kernel:
    quantize per-row (x) / per-col (w), integer matmul, fused dequant."""
    M, K = x.shape
    N = w.shape[1]
    xq, sx, zx = _ref.quantize_rows(x, 8)
    bits = 4 if w_bits <= 4 else 8
    wq, sw, zw = _ref.quantize_cols(w, bits)
    interpret = not _on_tpu()
    bm = bk = bn = 256
    xq = _pad_to(_pad_to(xq, bm, 0), bk, 1)
    wq_f = _pad_to(_pad_to(wq, bk, 0), bn, 1)
    sx_p = _pad_to(sx, bm, 0)
    zx_p = _pad_to(zx, bm, 0)
    sw_p = _pad_to(sw, bn, 0)
    zw_p = _pad_to(zw, bn, 0)
    if bits == 4:
        wq_f = _ref.pack_int4(wq_f)
    y = _qm.quant_matmul(xq, wq_f, sx_p, zx_p, sw_p, zw_p,
                         packed=(bits == 4), bm=bm, bk=bk, bn=bn,
                         out_dtype=out_dtype, k_true=K, interpret=interpret)
    return y[:M, :N]


_FQ_MAX_BR = 512    # fake-quant row block: <= 1 MiB of f32 at bc = 512
_FQ_MIN_BR = 64


def _fq_row_block(R: int) -> int:
    """Row block for ``fake_quant_2d``: all of R when it fits one block,
    else the largest multiple of 8 in [_FQ_MIN_BR, _FQ_MAX_BR] dividing
    R, else _FQ_MAX_BR (the wrapper then pads R)."""
    if R <= _FQ_MAX_BR:
        return R
    for br in range(_FQ_MAX_BR, _FQ_MIN_BR - 1, -_SUBLANE):
        if R % br == 0:
            return br
    return _FQ_MAX_BR


@jax.jit
def fused_fake_quant(x: jnp.ndarray, bits) -> jnp.ndarray:
    """Per-channel (last axis) fake quant of an arbitrary-rank tensor.

    Channels are zero-padded to a lane multiple and rows to a row-block
    multiple by repeating the last row; both are exact, since a channel's
    range reduces over its own rows only and a repeated row changes no
    min or max. The padding is sliced off the result."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    R, C = x2.shape
    br = _fq_row_block(R)
    xp = _pad_to(x2, _LANE, 1)
    if R % br:
        xp = jnp.pad(xp, ((0, (-R) % br), (0, 0)), mode="edge")
    Cp = xp.shape[1]
    bc = next(b for b in (512, 384, 256, _LANE) if Cp % b == 0)
    out = _fq.fake_quant_2d(xp, bits, br=br, bc=bc,
                            interpret=not _on_tpu())
    return out[:R, :C].reshape(shape)


# --------------------------------------------------------------------------
# Fused DDPG update-path kernels (ISSUE 7): 3-layer MLP forward + flat
# Polyak. The forward runs in the Pallas kernel; gradients come from a
# ``custom_vjp`` whose backward is the reference jnp chain (pallas_call has
# no differentiation rule), so ``jax.grad`` through ``actor_forward`` /
# ``critic_forward`` works unchanged when the kernel path is routed.
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mlp3_ste(sigmoid: bool, x, w1, b1, w2, b2, w3, b3):
    y, _, _ = _mlp3_fwd_impl(sigmoid, x, w1, b1, w2, b2, w3, b3)
    return y


def _mlp3_fwd_impl(sigmoid, x, w1, b1, w2, b2, w3, b3):
    """Pad to kernel-legal tiles, run the fused kernel, slice back.

    Zero padding is exact here: padded x columns hit zero W rows, padded
    b entries are zero, and relu(0)=0 keeps padded hidden columns zero —
    see kernels.mlp_fused."""
    B, D0 = x.shape
    D1, D2, D3 = w1.shape[1], w2.shape[1], w3.shape[1]
    xp = _pad_to(_pad_to(x, _SUBLANE, 0), _LANE, 1)
    w1p = _pad_to(_pad_to(w1, _LANE, 0), _LANE, 1)
    w2p = _pad_to(_pad_to(w2, _LANE, 0), _LANE, 1)
    w3p = _pad_to(_pad_to(w3, _LANE, 0), _LANE, 1)
    b1p = _pad_to(b1.reshape(1, -1), _LANE, 1)
    b2p = _pad_to(b2.reshape(1, -1), _LANE, 1)
    b3p = _pad_to(b3.reshape(1, -1), _LANE, 1)
    y, h1, h2 = _mlp.mlp3(xp, w1p, b1p, w2p, b2p, w3p, b3p,
                          sigmoid=sigmoid, interpret=not _on_tpu())
    return y[:B, :D3], h1[:B, :D1], h2[:B, :D2]


def _mlp3_vjp_fwd(sigmoid, x, w1, b1, w2, b2, w3, b3):
    y, h1, h2 = _mlp3_fwd_impl(sigmoid, x, w1, b1, w2, b2, w3, b3)
    return y, (x, w1, w2, w3, h1, h2, y)


def _mlp3_vjp_bwd(sigmoid, res, dy):
    # reference jnp backward (relu' = z > 0 == h > 0; sigmoid' = y(1-y))
    x, w1, w2, w3, h1, h2, y = res
    dz3 = dy * y * (1.0 - y) if sigmoid else dy
    dw3 = h2.T @ dz3
    db3 = jnp.sum(dz3, axis=0)
    dz2 = (dz3 @ w3.T) * (h2 > 0)
    dw2 = h1.T @ dz2
    db2 = jnp.sum(dz2, axis=0)
    dz1 = (dz2 @ w2.T) * (h1 > 0)
    dw1 = x.T @ dz1
    db1 = jnp.sum(dz1, axis=0)
    dx = dz1 @ w1.T
    return dx, dw1, db1, dw2, db2, dw3, db3


_mlp3_ste.defvjp(_mlp3_vjp_fwd, _mlp3_vjp_bwd)


def fused_mlp3(params, x, final: str = "linear") -> jnp.ndarray:
    """Fused 3-layer MLP forward (one kernel, differentiable via the
    reference backward). ``params`` is the ddpg ``_mlp`` layout — a list
    of three ``{"w", "b"}`` layers; ``final`` is "linear" or "sigmoid"."""
    (l1, l2, l3) = params
    return _mlp3_ste(final == "sigmoid", x, l1["w"], l1["b"],
                     l2["w"], l2["b"], l3["w"], l3["b"])


_POLYAK_BR = 256    # polyak row block (rows of 128 lanes)


def fused_polyak(target, online, tau):
    """Soft-target update ``(1 - tau) * target + tau * online`` for an
    arbitrary pytree: both trees are flattened into ONE [R, 128] buffer,
    updated in a single kernel pass, and unflattened — instead of one
    dispatch per parameter leaf."""
    t_leaves, treedef = jax.tree.flatten(target)
    p_leaves = treedef.flatten_up_to(online)
    sizes = [l.size for l in t_leaves]
    flat_t = jnp.concatenate([l.reshape(-1) for l in t_leaves])
    flat_p = jnp.concatenate([l.reshape(-1) for l in p_leaves])
    n = flat_t.shape[0]
    # rows of 128 lanes, padded to a whole number of row blocks
    rows = -(-n // _LANE)
    br = _POLYAK_BR if rows > _POLYAK_BR else -(-rows // _SUBLANE) * _SUBLANE
    pad = (-(-rows // br) * br) * _LANE - n
    if pad:
        flat_t = jnp.pad(flat_t, (0, pad))
        flat_p = jnp.pad(flat_p, (0, pad))
    out = _mlp.polyak_flat(flat_t.reshape(-1, _LANE),
                           flat_p.reshape(-1, _LANE), tau, br=br,
                           interpret=not _on_tpu()).reshape(-1)[:n]
    offs, news = 0, []
    for leaf, size in zip(t_leaves, sizes):
        news.append(out[offs:offs + size].reshape(leaf.shape))
        offs += size
    return jax.tree.unflatten(treedef, news)


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, causal: bool = True,
                    window: int = 0) -> jnp.ndarray:
    """q [B,H,S,D]; k,v [B,KV,S,D]."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               interpret=not _on_tpu())


@jax.jit
def rglru_scan(a, b, h0=None):
    B, S, C = a.shape
    bs = 128
    while S % bs != 0:
        bs //= 2
    bc = 1024
    while C % bc != 0:
        bc //= 2
    return _rg.rglru_scan(a, b, h0, bs=max(bs, 1), bc=max(bc, 1),
                          interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(xh, dA, Bm, Cm, chunk: int = 256):
    S = xh.shape[1]
    c = min(chunk, S)
    while S % c != 0:
        c //= 2
    return _ssd.ssd_scan(xh, dA, Bm, Cm, chunk=max(c, 1),
                         interpret=not _on_tpu())
