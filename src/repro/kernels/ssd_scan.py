"""Mamba-2 SSD chunked-scan Pallas kernel (arXiv:2405.21060, listing 1).

Per (batch, head) the kernel walks chunks sequentially, carrying the
[P, N] state in VMEM scratch. Within a chunk (length L):

    A_cs   = cumsum(dA)                       [L]
    Lmat   = exp(segsum(dA))  (lower-tri)     [L, L]
    Y_diag = (C Bᵀ ⊙ Lmat) X                  intra-chunk, MXU
    Y_off  = diag(exp(A_cs)) C · state        inter-chunk contribution
    state  = exp(A_cs[-1]) · state + Bᵀ diag(exp(A_cs[-1]-A_cs)) X

Grid: (B, H, S/L) with the chunk axis innermost sequential. VMEM at
L=256, N=128, P=64: X 64KB + B/C 2×128KB + Lmat 256KB + state 32KB ≈ 0.6MB.
B and C are shared across heads (ngroups=1) — the index map broadcasts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, da_ref, b_ref, c_ref, o_ref, fin_ref, state_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    X = x_ref[0, :, 0].astype(jnp.float32)          # [L, P]
    dA = da_ref[0, :, 0].astype(jnp.float32)        # [L]
    Bm = b_ref[0].astype(jnp.float32)               # [L, N]
    Cm = c_ref[0].astype(jnp.float32)               # [L, N]

    A_cs = jnp.cumsum(dA)                           # [L]
    # segsum(dA)[i,j] = sum_{k=j+1..i} dA_k = A_cs[i] - A_cs[j]
    seg = A_cs[:, None] - A_cs[None, :]
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    Lmat = jnp.where(li >= lj, jnp.exp(seg), 0.0)   # includes diag = 1

    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    Y_diag = jax.lax.dot_general(scores * Lmat, X, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    state = state_ref[...]                          # [P, N]
    decay_out = jnp.exp(A_cs)[:, None]              # [L, 1]
    Y_off = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    Y_off = Y_off * decay_out                       # [L, P]

    total = jnp.exp(A_cs[-1])
    decay_st = jnp.exp(A_cs[-1] - A_cs)[:, None]    # [L, 1]
    upd = jax.lax.dot_general(X, Bm * decay_st, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    state_ref[...] = total * state + upd            # [P, N]

    o_ref[0, :, 0] = (Y_diag + Y_off).astype(o_ref.dtype)

    @pl.when(ci == pl.num_programs(2) - 1)
    def _final():
        fin_ref[0, 0] = state_ref[...].astype(fin_ref.dtype)


def ssd_scan(xh, dA, Bm, Cm, *, chunk: int = 256, interpret: bool = True):
    """xh [B,S,H,P] (dt-scaled inputs); dA [B,S,H] log decays;
    Bm, Cm [B,S,N]. Returns (y [B,S,H,P], final_state [B,H,P,N])."""
    B, S, H, P = xh.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    y, fin = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(B, H, S // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, S, H, P), xh.dtype),
                   jax.ShapeDtypeStruct((B, H, P, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xh, dA, Bm, Cm)
    return y, fin
