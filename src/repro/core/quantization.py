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

import os

import jax
import jax.numpy as jnp

# On-TPU truth (see DESIGN.md §1): MIX above 6 bits is never better than
# INT8 (same MXU path, worse packing), mirroring the paper's ARM finding.
MAX_MIX_BITS = 6

_fq_ops = None          # lazy kernels.ops handle (kernels import late —
                        # the kernel package must not load at model-import)


@jax.custom_jvp
def _fused_fake_quant_ste(xf: jnp.ndarray, bits) -> jnp.ndarray:
    """Kernel-backed quant-dequant with a straight-through JVP —
    ``pallas_call`` has no differentiation rule, so the identity
    tangent (exactly the STE) is attached here and ``jax.grad`` never
    traces into the kernel."""
    global _fq_ops
    if _fq_ops is None:
        from repro.kernels import ops
        _fq_ops = ops
    return _fq_ops.fused_fake_quant(xf, bits)


@_fused_fake_quant_ste.defjvp
def _fused_fake_quant_ste_jvp(primals, tangents):
    return _fused_fake_quant_ste(*primals), tangents[0]


def _kernel_route(x: jnp.ndarray, axis) -> bool:
    """True when this fake-quant call should run through the fused
    Pallas kernel (``kernels.ops.fused_fake_quant``): the kernel only
    implements the per-channel-last layout (range reduced over every
    non-final axis), and only a TPU backend compiles it to Mosaic —
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


def quantize(x: jnp.ndarray, bits, axis) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Paper Eq. 3: Q(r) = clip(floor(s*r - z), -n, n).

    Returns (q, scale, offset); all computed in f32.
    ``axis``: reduction axes for the dynamic range (per-channel = all axes
    except the channel one).
    """
    xf = x.astype(jnp.float32)
    bits = jnp.asarray(bits, jnp.float32)
    n = 2.0 ** bits - 1.0
    x_min, x_max = _minmax(xf, axis)
    s = n / (x_max - x_min)
    z = jnp.floor(s * x_min) + 2.0 ** (bits - 1.0)
    q = jnp.clip(jnp.floor(s * xf - z), -n, n)
    return q, s, z


def dequantize(q: jnp.ndarray, s: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    return (q + z + 0.5) / s  # +0.5: mid-rise reconstruction of the floor


def fake_quant(x: jnp.ndarray, bits, axis=None) -> jnp.ndarray:
    """Quantize-dequantize with straight-through gradients.

    ``bits`` may be a traced int scalar; bits >= 32 selects pass-through.
    ``axis=None`` -> per-channel over the LAST axis (paper: per channel).
    """
    if axis is None:
        axis = tuple(range(x.ndim - 1))
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    if _kernel_route(x, axis):
        # one-pass fused minmax/quant/dequant (bits >= 32 selects
        # pass-through inside the kernel)
        xq = _fused_fake_quant_ste(xf, jnp.asarray(bits, jnp.int32))
    else:
        q, s, z = quantize(xf, jnp.clip(jnp.asarray(bits), 1, 31), axis)
        xq = dequantize(q, s, z)
        xq = jnp.where(jnp.asarray(bits) >= 32, xf, xq)
    # Straight-through estimator: forward quantized values, identity grad.
    # The pass-through selects ``xf`` itself: ``xf + (xf - xf)`` flushes
    # denormals to zero, so 32 bits would not be the identity.
    out = jnp.where(jnp.asarray(bits) >= 32, xf,
                    xf + jax.lax.stop_gradient(xq - xf))
    return out.astype(orig_dtype)


def fake_quant_weight(w: jnp.ndarray, bits) -> jnp.ndarray:
    """Weights: per-OUTPUT-channel range (last axis is the out dim here)."""
    return fake_quant(w, bits, axis=tuple(range(w.ndim - 1)))


def fake_quant_act(x: jnp.ndarray, bits) -> jnp.ndarray:
    """Activations: per-channel over the feature (last) axis."""
    return fake_quant(x, bits, axis=tuple(range(x.ndim - 1)))


def bits_for_mode(mode: str, mix_bits: int = MAX_MIX_BITS) -> int:
    return {"FP32": 32, "INT8": 8, "MIX": mix_bits}[mode]
