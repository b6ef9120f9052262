"""Fake quantization (paper Eq. 3) — asymmetric uniform, dynamic per-channel
range, straight-through estimator for QAT.

The paper's three layer modes map to effective bit widths:
    FP32 -> bits = 32 (pass-through)
    INT8 -> bits = 8
    MIX  -> bits in [1, MAX_MIX_BITS]  (weights and activations independent)

Bit widths are carried as (possibly traced) int32 scalars so a whole
compression policy can flow through a ``lax.scan`` over stacked layers; the
``bits >= 32`` pass-through is a ``jnp.where`` select, not Python control
flow. When a model is built *without* a policy the quant path is skipped
statically (zero overhead for the uncompressed dry-run).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

# On-TPU truth (see DESIGN.md §1): MIX above 6 bits is never better than
# INT8 (same MXU path, worse packing), mirroring the paper's ARM finding.
MAX_MIX_BITS = 6

_fq_ops = None          # lazy kernels.ops handle (kernels import late —
                        # the kernel package must not load at model-import)


def _kernel_ops():
    global _fq_ops
    if _fq_ops is None:
        from repro.kernels import ops
        _fq_ops = ops
    return _fq_ops


@functools.partial(jax.custom_jvp, nondiff_argnums=(4,))
def _fake_quant_apply_ste(x: jnp.ndarray, bits, mn: jnp.ndarray,
                          mx: jnp.ndarray, dtype) -> jnp.ndarray:
    """The kernel's quantize-dequantize pass, which writes the
    straight-through value in ``dtype``, with a straight-through JVP —
    ``pallas_call`` has no differentiation rule, so the identity tangent
    (exactly the STE) is attached here and ``jax.grad`` never traces
    into the kernel. The range takes no tangent."""
    return _kernel_ops().fake_quant_apply(x, bits, mn, mx, out_dtype=dtype)


@_fake_quant_apply_ste.defjvp
def _fake_quant_apply_ste_jvp(dtype, primals, tangents):
    return (_fake_quant_apply_ste(*primals, dtype),
            tangents[0].astype(dtype))


def _kernel_route(x: jnp.ndarray, axis) -> bool:
    """True when this fake-quant call should run through the Pallas
    kernels (``kernels.ops.fake_quant_range``, ``fake_quant_apply``):
    they only implement the per-channel-last layout (range reduced over
    every non-final axis), and only a TPU backend compiles them to Mosaic —
    everywhere else the reference jnp path stays the default.
    ``GALEN_FQ_KERNEL=1`` forces the kernel (interpreted off-TPU, for
    parity tests); ``GALEN_FQ_KERNEL=0`` forces the reference path even
    on TPU. The route is resolved at trace time, so already-compiled
    functions keep their path."""
    axes = axis if isinstance(axis, (tuple, list)) else (axis,)
    if x.ndim < 2 or tuple(axes) != tuple(range(x.ndim - 1)):
        return False
    v = os.environ.get("GALEN_FQ_KERNEL")
    if v is not None:
        return v == "1"
    return jax.default_backend() == "tpu"


def _minmax(x: jnp.ndarray, axis) -> tuple[jnp.ndarray, jnp.ndarray]:
    x_min = jnp.min(x, axis=axis, keepdims=True)
    x_max = jnp.max(x, axis=axis, keepdims=True)
    # Guard degenerate (constant) channels.
    span = jnp.maximum(x_max - x_min, 1e-8)
    return x_min, x_min + span


def _quantize_in_range(xf: jnp.ndarray, bits, x_min: jnp.ndarray,
                       x_max: jnp.ndarray):
    bits = jnp.asarray(bits, jnp.float32)
    n = 2.0 ** bits - 1.0
    s = n / (x_max - x_min)
    z = jnp.floor(s * x_min) + 2.0 ** (bits - 1.0)
    q = jnp.clip(jnp.floor(s * xf - z), -n, n)
    return q, s, z


def quantize(x: jnp.ndarray, bits, axis) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Paper Eq. 3: Q(r) = clip(floor(s*r - z), -n, n).

    Returns (q, scale, offset); all computed in f32.
    ``axis``: reduction axes for the dynamic range (per-channel = all axes
    except the channel one).
    """
    xf = x.astype(jnp.float32)
    return _quantize_in_range(xf, bits, *_minmax(xf, axis))


def dequantize(q: jnp.ndarray, s: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    return (q + z + 0.5) / s  # +0.5: mid-rise reconstruction of the floor


def _reference_fq(xf: jnp.ndarray, bits, x_min: jnp.ndarray,
                  x_max: jnp.ndarray) -> jnp.ndarray:
    """The reference quantize-dequantize of f32 ``xf`` with a per-channel
    range; bits >= 32 selects ``xf``."""
    q, s, z = _quantize_in_range(xf, jnp.clip(jnp.asarray(bits), 1, 31),
                                 x_min, x_max)
    return jnp.where(jnp.asarray(bits) >= 32, xf, dequantize(q, s, z))


def _straight_through(xf: jnp.ndarray, xq: jnp.ndarray, bits) -> jnp.ndarray:
    """Forward the quantized values, identity gradient. The pass-through
    selects ``xf`` itself: ``xf + (xf - xf)`` flushes denormals to zero,
    so 32 bits would not be the identity."""
    return jnp.where(jnp.asarray(bits) >= 32, xf,
                     xf + jax.lax.stop_gradient(xq - xf))


def _fake_quant_in_range(x: jnp.ndarray, bits, src: jnp.ndarray, axis,
                         dtype) -> jnp.ndarray:
    """``x`` quantize-dequantized with the per-channel range of ``src``
    (``x`` itself, or the tensor x's rows were taken from), reduced over
    ``axis``, with straight-through gradients, in ``dtype``; the range
    takes no gradient. bits >= 32 selects ``x``. The kernel route reads
    both in their stored dtype and writes the straight-through value in
    ``dtype`` itself; the reference route computes it in f32 and casts."""
    src = jax.lax.stop_gradient(src)
    if _kernel_route(src, axis):
        mn, mx = _kernel_ops().fake_quant_range(src)
        return _fake_quant_apply_ste(x, jnp.asarray(bits, jnp.int32),
                                     mn, mx, jnp.dtype(dtype))
    xf = x.astype(jnp.float32)
    xq = _reference_fq(xf, bits, *_minmax(src.astype(jnp.float32), axis))
    return _straight_through(xf, xq, bits).astype(dtype)


def fake_quant(x: jnp.ndarray, bits, axis=None, dtype=None) -> jnp.ndarray:
    """Quantize-dequantize with straight-through gradients.

    ``bits`` may be a traced int scalar; bits >= 32 selects pass-through.
    ``axis=None`` -> per-channel over the LAST axis (paper: per channel).
    ``dtype``: the dtype of the consumer, e.g. the matmul that reads the
    result (None: x's dtype); the result is rounded to it once.
    """
    if axis is None:
        axis = tuple(range(x.ndim - 1))
    # the scope tags these ops in a profiler trace (metadata only)
    with jax.named_scope("fake_quant"):
        return _fake_quant_in_range(
            x, bits, x, axis, x.dtype if dtype is None else dtype)


def fake_quant_rows(table: jnp.ndarray, idx: jnp.ndarray, bits,
                    dtype=None) -> jnp.ndarray:
    """``jnp.take(fake_quant_weight(table, bits, dtype), idx, axis=0)``,
    bit for bit and in gradient, with the quantize-dequantize run on the
    taken rows only: the embedding lookup reads a few hundred rows of a
    vocabulary-sized table. The per-channel range is still the whole
    table's (policy-independent, so a vmap over bit widths computes it
    once)."""
    with jax.named_scope("fake_quant"), jax.named_scope("embed_rows"):
        return _fake_quant_in_range(jnp.take(table, idx, axis=0), bits,
                                    table, tuple(range(table.ndim - 1)),
                                    table.dtype if dtype is None else dtype)


def fake_quant_weight(w: jnp.ndarray, bits, dtype=None) -> jnp.ndarray:
    """Weights: per-OUTPUT-channel range (last axis is the out dim here),
    in the matmul's ``dtype`` (None: w's)."""
    return fake_quant(w, bits, axis=tuple(range(w.ndim - 1)), dtype=dtype)


def fake_quant_act(x: jnp.ndarray, bits, dtype=None) -> jnp.ndarray:
    """Activations: per-channel over the feature (last) axis, in the
    matmul's ``dtype`` (None: x's)."""
    return fake_quant(x, bits, axis=tuple(range(x.ndim - 1)), dtype=dtype)


def bits_for_mode(mode: str, mix_bits: int = MAX_MIX_BITS) -> int:
    return {"FP32": 32, "INT8": 8, "MIX": mix_bits}[mode]
