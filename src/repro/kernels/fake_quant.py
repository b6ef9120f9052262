"""Fake-quantization Pallas kernels (paper Eq. 3).

Layout: x viewed as [R, C] with the channel axis LAST and the dynamic-range
reduction over axis 0 (rows) — matching ``core.quantization.fake_quant``.

Two passes, each tiled over (rows, channels) so VMEM use is bounded by the
block shape whatever R is (an activation slab or a vocab-sized embedding
table has far more rows than one block can hold):

1. ``_range_kernel`` reduces per-channel min/max over row blocks. The row
   axis is the innermost grid axis and the (1, bc) min/max outputs stay
   resident across it, accumulating.
2. ``_quant_kernel`` derives scale/offset from the range and writes the
   quantize-clip-dequantize of each (br, bc) block.

So x is read twice and written once. Shapes must be kernel-legal before
the call (``kernels.ops.fused_fake_quant`` pads them): C a multiple of
``bc`` (itself a multiple of 128) and R a multiple of ``br`` (a multiple
of 8, or R itself). ``bits`` is an int32 scalar in SMEM, held as a
(1, 1) array so that a vmapped bit width stays a legal block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _range_kernel(x_ref, mn_ref, mx_ref):
    x = x_ref[...].astype(jnp.float32)
    bmin = jnp.min(x, axis=0, keepdims=True)
    bmax = jnp.max(x, axis=0, keepdims=True)

    @pl.when(pl.program_id(1) == 0)
    def _():
        mn_ref[...] = bmin
        mx_ref[...] = bmax

    @pl.when(pl.program_id(1) > 0)
    def _():
        mn_ref[...] = jnp.minimum(mn_ref[...], bmin)
        mx_ref[...] = jnp.maximum(mx_ref[...], bmax)


def _quant_kernel(bits_ref, x_ref, mn_ref, mx_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    bits = bits_ref[0, 0].astype(jnp.float32)
    b = jnp.clip(bits, 1.0, 31.0)
    n = 2.0 ** b - 1.0
    # the reference's arithmetic (core.quantization._minmax / quantize),
    # operation for operation
    x_min = mn_ref[...]
    x_max = x_min + jnp.maximum(mx_ref[...] - x_min, 1e-8)
    s = n / (x_max - x_min)
    z = jnp.floor(s * x_min) + 2.0 ** (b - 1.0)
    q = jnp.clip(jnp.floor(s * x - z), -n, n)
    deq = (q + z + 0.5) / s
    # bits >= 32 passes x through untouched (a select, no arithmetic, so
    # denormals survive)
    o_ref[...] = jnp.where(bits >= 32.0, x, deq).astype(o_ref.dtype)


def fake_quant_2d(x: jnp.ndarray, bits, *, br: int, bc: int,
                  interpret: bool = True) -> jnp.ndarray:
    """x [R, C]: quantize-dequantize with per-channel (last axis) dynamic
    range reduced over axis 0, on (br, bc) blocks. ``bits`` may be a
    traced int scalar."""
    R, C = x.shape
    grid_r, grid_c = R // br, C // bc
    mn, mx = pl.pallas_call(
        _range_kernel,
        grid=(grid_c, grid_r),
        in_specs=[pl.BlockSpec((br, bc), lambda j, i: (i, j))],
        out_specs=[pl.BlockSpec((1, bc), lambda j, i: (0, j)),
                   pl.BlockSpec((1, bc), lambda j, i: (0, j))],
        out_shape=[jax.ShapeDtypeStruct((1, C), jnp.float32),
                   jax.ShapeDtypeStruct((1, C), jnp.float32)],
        interpret=interpret,
    )(x)
    bits_arr = jnp.reshape(jnp.asarray(bits, jnp.int32), (1, 1))
    return pl.pallas_call(
        _quant_kernel,
        grid=(grid_r, grid_c),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((br, bc), lambda i, j: (i, j)),
                  pl.BlockSpec((1, bc), lambda i, j: (0, j)),
                  pl.BlockSpec((1, bc), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        interpret=interpret,
    )(bits_arr, x, mn, mx)
