"""Arithmetic of the plain references, in one of three precisions.

``f32`` is the reference itself: float32 everywhere, matrix products at
``HIGHEST`` precision (a TPU otherwise computes a float32 product in
bfloat16 passes). ``bf16`` and ``int8`` are the controls that stand one
step below a configuration's stated precision: bfloat16 arithmetic, and
bfloat16 arithmetic with every matrix product taken over int8 operands
(symmetric, one scale per row of the left and per column of the right
operand).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "bf16", "int8")


class Numerics:
    def __init__(self, name: str = "f32"):
        if name not in PRECISIONS:
            raise ValueError(f"unknown precision {name!r}")
        self.name = name
        self.dtype = jnp.float32 if name == "f32" else jnp.bfloat16

    def cast(self, x):
        return jnp.asarray(x).astype(self.dtype)

    def mm(self, spec: str, a, b):
        """``einsum(spec, a, b)`` contracting one axis, in this precision."""
        if self.name == "f32":
            return jnp.einsum(spec, a.astype(jnp.float32),
                              b.astype(jnp.float32),
                              precision=jax.lax.Precision.HIGHEST)
        if self.name == "bf16":
            return jnp.einsum(spec, a.astype(jnp.bfloat16),
                              b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32
                              ).astype(jnp.bfloat16)
        qa, sa = _int8(a, spec, 0)
        qb, sb = _int8(b, spec, 1)
        acc = jnp.einsum(spec, qa, qb, preferred_element_type=jnp.int32)
        out = acc.astype(jnp.float32) * _outer_scale(spec, sa, sb)
        return out.astype(jnp.bfloat16)


def _contracted(spec: str):
    ins, out = spec.split("->")
    a, b = ins.split(",")
    return [c for c in a if c in b and c not in out][0], a, b, out


def _int8(x, spec: str, which: int):
    c, a, b, _ = _contracted(spec)
    sub = (a, b)[which]
    axis = sub.index(c)
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=axis, keepdims=True),
                    1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / s), -127, 127).astype(jnp.int8)
    return q, jnp.squeeze(s, axis)


def _outer_scale(spec: str, sa, sb):
    c, a, b, out = _contracted(spec)
    a_keep = "".join(ch for ch in a if ch != c)
    b_keep = "".join(ch for ch in b if ch != c)
    return jnp.einsum(f"{a_keep},{b_keep}->{out}", sa, sb)
