"""Quantized matmul Pallas kernels — the TPU replacement for the paper's
ARM bit-serial operators (DESIGN.md §1).

Two entry kernels:

* ``int8_matmul_kernel``   — int8 x int8 -> int32 on the MXU with fused
  asymmetric dequantization (per-row activation scale/zero, per-column
  weight scale/zero). Convention: zero offsets are ADDED back on
  dequantization, x = sx·(xq + zx) and w = sw·(wq + zw), so
      y[m,n] = sx[m]·sw[n]·(acc[m,n] + zx[m]·Σ_k wq[k,n]
                            + zw[n]·Σ_k xq[m,k] + K·zx[m]·zw[n])
  (matches ``_dequant_epilogue`` and ``ref.int8_matmul_ref``; locked by
  the asymmetric zero-point test in tests/test_kernels.py).
* ``int4_matmul_kernel``   — weights stored packed two-per-byte (the MIX
  ≤4-bit policy path); unpacked in-VMEM, then the same int8 MXU pipeline.
  The win is HBM/ICI traffic (half of int8), not FLOPs — exactly the
  hardware truth the latency oracle teaches the agent.

Tiling: (bm × bk) x (bk × bn) blocks, K innermost ("arbitrary") grid dim
accumulating into an int32 VMEM scratch; dequant epilogue on the last K
step. All dims must be multiples of the block shape — ``ops.py`` pads.
VMEM at defaults (bm=bk=bn=256): x 64KB + w 64KB + acc 256KB + out 128KB
≈ 0.5MB, comfortably inside the ~16MB/core budget; MXU dims 128-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 256
DEFAULT_BK = 256
DEFAULT_BN = 256


def _dequant_epilogue(acc, xsum_blk, wsum_blk, sx, zx, sw, zw, k_total):
    """acc int32 [bm,bn]; sums int32; scales f32. Returns f32 [bm,bn].
    Convention (paper Eq. 3): x = sx·(xq + zx), w = sw·(wq + zw), so
    Σ x·w = sx·sw·(acc + zx·Σwq + zw·Σxq + K·zx·zw)."""
    accf = acc.astype(jnp.float32)
    corr = (accf
            + zx[:, None] * wsum_blk[None, :].astype(jnp.float32)
            + zw[None, :] * xsum_blk[:, None].astype(jnp.float32)
            + k_total * zx[:, None] * zw[None, :])
    return sx[:, None] * sw[None, :] * corr


def int8_matmul_kernel(xq_ref, wq_ref, sx_ref, zx_ref, sw_ref, zw_ref,
                       o_ref, acc_ref, xsum_ref, wsum_ref, *, k_total: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xsum_ref[...] = jnp.zeros_like(xsum_ref)
        wsum_ref[...] = jnp.zeros_like(wsum_ref)

    xq = xq_ref[...]
    wq = wq_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        xq, wq, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    xsum_ref[...] += jnp.sum(xq.astype(jnp.int32), axis=1)
    wsum_ref[...] += jnp.sum(wq.astype(jnp.int32), axis=0)

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        y = _dequant_epilogue(acc_ref[...], xsum_ref[...], wsum_ref[...],
                              sx_ref[...], zx_ref[...],
                              sw_ref[...], zw_ref[...], k_total)
        o_ref[...] = y.astype(o_ref.dtype)


def unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
    """[K//2, N] int8 (two nibbles per byte along K) -> [K, N] int8 in
    [-8, 7]. Layout: byte b holds rows 2b (low nibble) and 2b+1 (high)."""
    low = jnp.left_shift(packed, 4)
    low = jnp.right_shift(low, 4)                    # sign-extend low nibble
    high = jnp.right_shift(packed, 4)                # arithmetic shift
    kk, n = packed.shape
    out = jnp.stack([low, high], axis=1).reshape(2 * kk, n)
    return out.astype(jnp.int8)


def int4_matmul_kernel(xq_ref, wp_ref, sx_ref, zx_ref, sw_ref, zw_ref,
                       o_ref, acc_ref, xsum_ref, wsum_ref, *, k_total: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xsum_ref[...] = jnp.zeros_like(xsum_ref)
        wsum_ref[...] = jnp.zeros_like(wsum_ref)

    xq = xq_ref[...]
    wq = unpack_int4(wp_ref[...])                    # in-VMEM unpack
    acc_ref[...] += jax.lax.dot_general(
        xq, wq, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    xsum_ref[...] += jnp.sum(xq.astype(jnp.int32), axis=1)
    wsum_ref[...] += jnp.sum(wq.astype(jnp.int32), axis=0)

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        y = _dequant_epilogue(acc_ref[...], xsum_ref[...], wsum_ref[...],
                              sx_ref[...], zx_ref[...],
                              sw_ref[...], zw_ref[...], k_total)
        o_ref[...] = y.astype(o_ref.dtype)


def _specs(bm, bk, bn, packed_w: bool):
    kw = 2 if packed_w else 1
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),          # xq
        pl.BlockSpec((bk // kw, bn), lambda i, j, k: (k, j)),    # wq / packed
        pl.BlockSpec((bm,), lambda i, j, k: (i,)),               # sx
        pl.BlockSpec((bm,), lambda i, j, k: (i,)),               # zx
        pl.BlockSpec((bn,), lambda i, j, k: (j,)),               # sw
        pl.BlockSpec((bn,), lambda i, j, k: (j,)),               # zw
    ]
    out_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    return in_specs, out_spec


def quant_matmul(xq, wq, sx, zx, sw, zw, *, packed: bool = False,
                 bm: int = DEFAULT_BM, bk: int = DEFAULT_BK,
                 bn: int = DEFAULT_BN, out_dtype=jnp.float32,
                 k_true: int = 0, interpret: bool = True):
    """xq [M,K] int8; wq [K,N] int8 or [K//2,N] packed int4; scales f32.

    ``k_true``: the UNPADDED contraction length — the K·zx·zw zero-point
    correction must not count zero-padded rows (their xq=wq=0 entries add
    nothing to acc or the sums, but a padded K would overcount this term).
    """
    M, K = xq.shape
    N = wq.shape[1]
    if packed:
        assert wq.shape[0] * 2 == K, (wq.shape, K)
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bk, bn)
    kern = int4_matmul_kernel if packed else int8_matmul_kernel
    in_specs, out_spec = _specs(bm, bk, bn, packed)
    return pl.pallas_call(
        functools.partial(kern, k_total=k_true or K),
        grid=(M // bm, N // bn, K // bk),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32),
                        pltpu.VMEM((bm,), jnp.int32),
                        pltpu.VMEM((bn,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xq, wq, sx, zx, sw, zw)
