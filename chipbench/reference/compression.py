"""Galen's compression arithmetic, written from the paper and the
repository's documented semantics without its code.

* Fake quantization (paper Eq. 3, asymmetric uniform, dynamic range per
  channel of the last axis, reduced over every other axis):
  ``n = 2^b - 1``, ``s = n / (max - min)`` (the span floored at 1e-8),
  ``z = floor(s * min) + 2^(b-1)``, ``q = clip(floor(s x - z), -n, n)``,
  ``x' = (q + z + 0.5) / s``. A width of 32 bits or more passes ``x``
  through; widths below 1 count as 1.
* Structured pruning keeps the ``keep`` channels with the largest l1
  score, the lower index first among equal scores.
* A policy's effective widths: FP32 is (32, 32), INT8 is (8, 8), MIX
  carries its own (w, a).
"""
from __future__ import annotations

import jax.numpy as jnp


def fake_quant(x, bits, num, axes=None):
    """Quantize-dequantize ``x`` at ``bits`` (a traced scalar) in the
    precision ``num``; the range is taken per channel of the last axis."""
    if axes is None:
        axes = tuple(range(x.ndim - 1))
    xf = num.cast(x)
    b = jnp.clip(jnp.asarray(bits), 1, 31).astype(num.dtype)
    n = 2.0 ** b - 1.0
    lo = jnp.min(xf, axis=axes, keepdims=True)
    hi = lo + jnp.maximum(jnp.max(xf, axis=axes, keepdims=True) - lo,
                          jnp.asarray(1e-8, num.dtype))
    s = n / (hi - lo)
    z = jnp.floor(s * lo) + 2.0 ** (b - 1.0)
    q = jnp.clip(jnp.floor(s * xf - z), -n, n)
    deq = (q + z + 0.5) / s
    return jnp.where(jnp.asarray(bits) >= 32, xf, deq.astype(num.dtype))


def keep_mask(scores, keep):
    """0/1 float mask of the ``keep`` highest scores (lower index first
    on ties); ``keep`` may be traced."""
    n = scores.shape[0]
    order = jnp.argsort(-scores, stable=True)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    return (rank < jnp.clip(keep, 0, n)).astype(jnp.float32)


def effective_bits(mode: str, w_bits: int, a_bits: int):
    if mode == "FP32":
        return 32, 32
    if mode == "INT8":
        return 8, 8
    return int(w_bits), int(a_bits)
