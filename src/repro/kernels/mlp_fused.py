"""Fused 3-layer MLP forward + flat Polyak Pallas kernels — the DDPG
update path's compute (ISSUE 7).

``mlp3`` runs the whole actor/critic trunk
``x @ W1 + b1 -> relu -> @ W2 + b2 -> relu -> @ W3 + b3 [-> sigmoid]``
as ONE kernel: the weights live in VMEM for the whole grid and the
intermediate activations never round-trip through HBM — on TPU the three
GEMMs feed the MXU back to back instead of dispatching three tiny
(B, 400)x(400, 300)-class matmuls with HBM writes between them. The
hidden activations h1/h2 are emitted as extra outputs so a reference
``custom_vjp`` backward (kernels.ops.fused_mlp3) can reuse them.

``polyak`` is the soft-target update ``t = (1 - tau) * t + tau * p`` over
a FLATTENED parameter buffer: one elementwise kernel pass over the whole
network instead of one dispatch per parameter leaf.

Shapes must be kernel-legal before the call: callers (kernels.ops) pad
the batch axis to the f32 sublane multiple (8) and every feature axis to
the lane multiple (128). Zero padding is correctness-preserving here:
padded x columns meet padded (zero) W rows, padded b entries are zero,
and ``relu(0) = 0`` keeps padded hidden columns zero through the stack —
only the final sigmoid makes padded output columns nonzero (0.5), which
the wrapper slices away.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mlp3_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, w3_ref, b3_ref,
                 y_ref, h1_ref, h2_ref, *, sigmoid: bool):
    x = x_ref[...].astype(jnp.float32)
    h1 = jnp.maximum(
        jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
        + b1_ref[...], 0.0)
    h2 = jnp.maximum(
        jnp.dot(h1, w2_ref[...], preferred_element_type=jnp.float32)
        + b2_ref[...], 0.0)
    y = jnp.dot(h2, w3_ref[...], preferred_element_type=jnp.float32) \
        + b3_ref[...]
    if sigmoid:
        y = jax.nn.sigmoid(y)
    y_ref[...] = y.astype(y_ref.dtype)
    h1_ref[...] = h1.astype(h1_ref.dtype)
    h2_ref[...] = h2.astype(h2_ref.dtype)


def mlp3(x, w1, b1, w2, b2, w3, b3, *, sigmoid: bool = False,
         bm: int = 128, interpret: bool = True):
    """Fused 3-layer MLP forward on pre-padded operands.

    x [B, D0]; wi [D(i-1), Di]; bi [1, Di] (2D so the lane layout is
    explicit). Returns ``(y [B, D3], h1 [B, D1], h2 [B, D2])`` — the
    hidden activations are the residuals the reference backward needs.
    The grid tiles the batch axis only; every weight block is the whole
    (padded) matrix, resident in VMEM across the grid.
    """
    B, D0 = x.shape
    D1, D2, D3 = w1.shape[1], w2.shape[1], w3.shape[1]
    bm = min(bm, B)
    while B % bm != 0:          # fall back to a divisor of B
        bm -= 1
    import functools
    kern = functools.partial(_mlp3_kernel, sigmoid=sigmoid)
    full = lambda a, b: pl.BlockSpec((a, b), lambda i: (0, 0))
    return pl.pallas_call(
        kern,
        grid=(B // bm,),
        in_specs=[pl.BlockSpec((bm, D0), lambda i: (i, 0)),
                  full(D0, D1), full(1, D1),
                  full(D1, D2), full(1, D2),
                  full(D2, D3), full(1, D3)],
        out_specs=[pl.BlockSpec((bm, D3), lambda i: (i, 0)),
                   pl.BlockSpec((bm, D1), lambda i: (i, 0)),
                   pl.BlockSpec((bm, D2), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, D3), x.dtype),
                   jax.ShapeDtypeStruct((B, D1), x.dtype),
                   jax.ShapeDtypeStruct((B, D2), x.dtype)],
        interpret=interpret,
    )(x, w1, b1, w2, b2, w3, b3)


def _polyak_kernel(tau_ref, t_ref, p_ref, o_ref):
    tau = tau_ref[0, 0]
    o_ref[...] = ((1.0 - tau) * t_ref[...]
                  + tau * p_ref[...]).astype(o_ref.dtype)


def polyak_flat(target, online, tau, *, br: int, interpret: bool = True):
    """``(1 - tau) * target + tau * online`` over [R, 128] flat views —
    the whole network's soft-target update as one kernel pass. ``br``
    (a multiple of 8, or R itself) must divide R; ``tau`` is an f32
    scalar in SMEM."""
    R, C = target.shape
    tau_arr = jnp.reshape(jnp.asarray(tau, jnp.float32), (1, 1))
    return pl.pallas_call(
        _polyak_kernel,
        grid=(R // br,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((br, C), lambda i: (i, 0)),
                  pl.BlockSpec((br, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), target.dtype),
        interpret=interpret,
    )(tau_arr, target, online)
