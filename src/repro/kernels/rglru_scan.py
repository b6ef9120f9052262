"""RG-LRU linear-recurrence Pallas kernel (RecurrentGemma substrate).

h_t = a_t ⊙ h_{t-1} + b_t — a diagonal linear recurrence. The jnp
reference uses ``associative_scan`` (log-depth, but materializes O(S)
intermediates and round-trips HBM per level); this kernel streams time
blocks through VMEM sequentially, carrying the state vector in scratch —
one HBM read of (a, b) and one write of h total.

Grid: (B, C/bc, S/bs) with time innermost sequential; channel blocks are
independent (diagonal recurrence). VMEM: 3 × bs×bc × 4B ≈ 1.5MB at
bs=128, bc=1024, + state bc.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, state_ref, *, bs: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        state_ref[...] = h0_ref[0]

    a = a_ref[0].astype(jnp.float32)            # [bs, bc]
    b = b_ref[0].astype(jnp.float32)
    h = state_ref[...]                          # [bc]

    # sequential within the block (bs small; unrolled by the compiler)
    def step(i, carry):
        h, out = carry
        h = a[i] * h + b[i]
        out = out.at[i].set(h)
        return h, out

    out0 = jnp.zeros_like(a)
    h, out = jax.lax.fori_loop(0, bs, step, (h, out0))
    state_ref[...] = h
    o_ref[0] = out.astype(o_ref.dtype)


def rglru_scan(a, b, h0=None, *, bs: int = 128, bc: int = 1024,
               interpret: bool = True):
    """a, b: [B, S, C]; h0: [B, C] initial state. Returns h: [B, S, C]."""
    B, S, C = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, C), jnp.float32)
    bs, bc = min(bs, S), min(bc, C)
    assert S % bs == 0 and C % bc == 0, (S, C, bs, bc)
    return pl.pallas_call(
        functools.partial(_rglru_kernel, bs=bs),
        grid=(B, C // bc, S // bs),
        in_specs=[pl.BlockSpec((1, bs, bc), lambda bi, ci, ti: (bi, ti, ci)),
                  pl.BlockSpec((1, bs, bc), lambda bi, ci, ti: (bi, ti, ci)),
                  pl.BlockSpec((1, bc), lambda bi, ci, ti: (bi, ci))],
        out_specs=pl.BlockSpec((1, bs, bc), lambda bi, ci, ti: (bi, ti, ci)),
        out_shape=jax.ShapeDtypeStruct((B, S, C), a.dtype),
        scratch_shapes=[pltpu.VMEM((bc,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0)
