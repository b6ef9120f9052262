"""Property tests for fake quantization (paper Eq. 3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st
from hypothesis.extra import numpy as hnp

from repro.core.quantization import fake_quant, fake_quant_weight, quantize

arrays = hnp.arrays(np.float32, hnp.array_shapes(min_dims=2, max_dims=2,
                                                 min_side=2, max_side=32),
                    elements=st.floats(-100, 100, width=32))


@given(arrays, st.integers(1, 8))
@settings(max_examples=40, deadline=None)
def test_quant_error_bound(x, bits):
    """|fq(x) - x| <= quantization step (per channel)."""
    x = jnp.asarray(x)
    span = jnp.max(x, 0) - jnp.min(x, 0)
    # mask near-constant channels at large magnitude: f32 cancellation in
    # s*x - z dominates there and the step bound is meaningless
    ok = span >= 1e-3 * (jnp.max(jnp.abs(x), 0) + 1e-3)
    out = fake_quant(x, bits, axis=(0,))
    step = jnp.maximum(span, 1e-8) / (2.0 ** bits - 1.0)
    err = jnp.abs(out - x)
    bound = step + 1e-3 * span + 1e-6
    assert bool(jnp.all(jnp.where(ok[None], err <= bound, True)))


@given(arrays)
@settings(max_examples=20, deadline=None)
def test_bits32_identity(x):
    x = jnp.asarray(x)
    out = fake_quant(x, 32, axis=(0,))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=1e-6)


@given(st.integers(1, 8))
@settings(max_examples=8, deadline=None)
def test_monotone_in_x(bits):
    """Uniform quantization is monotone non-decreasing."""
    x = jnp.sort(jax.random.normal(jax.random.PRNGKey(0), (256,)))
    out = fake_quant(x[None, :].T, bits, axis=(0,))  # single channel
    d = jnp.diff(out[:, 0])
    assert bool(jnp.all(d >= -1e-6))


def test_quant_values_in_range():
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 8)) * 10
    for bits in (2, 4, 8):
        q, s, z = quantize(x, bits, axis=(0,))
        n = 2.0 ** bits - 1
        assert bool(jnp.all(q >= -n)) and bool(jnp.all(q <= n))


def test_fewer_bits_more_error():
    x = jax.random.normal(jax.random.PRNGKey(2), (128, 16))
    errs = [float(jnp.mean(jnp.abs(fake_quant(x, b, axis=(0,)) - x)))
            for b in (2, 4, 8)]
    assert errs[0] > errs[1] > errs[2]


def test_straight_through_gradient():
    x = jax.random.normal(jax.random.PRNGKey(3), (32, 4))
    g = jax.grad(lambda t: jnp.sum(fake_quant(t, 4, axis=(0,))))(x)
    # STE: gradient is (close to) ones except range-edge interactions
    assert float(jnp.mean(jnp.abs(g - 1.0))) < 0.15


def test_traced_bits():
    """bits may be a traced scalar (needed inside lax.scan)."""
    x = jax.random.normal(jax.random.PRNGKey(4), (16, 8))

    @jax.jit
    def f(b):
        return fake_quant_weight(x, b)

    out8 = f(jnp.int32(8))
    out32 = f(jnp.int32(32))
    np.testing.assert_allclose(np.asarray(out32), np.asarray(x), rtol=1e-6)
    assert float(jnp.max(jnp.abs(out8 - x))) > 0


# ------------------------------------------------- Pallas kernel routing

def test_kernel_route_matches_ref(monkeypatch):
    """GALEN_FQ_KERNEL=1 sends per-channel-last fake_quant through the
    fused Pallas kernel (interpreted off-TPU): same forward values,
    same STE gradient, same bits>=32 pass-through as the ref path."""
    from repro.core.quantization import fake_quant_act
    monkeypatch.delenv("GALEN_FQ_KERNEL", raising=False)
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 32, 16)) * 3.0
    ref_out = fake_quant_act(x, 4)
    ref32 = fake_quant_act(x, 32)
    monkeypatch.setenv("GALEN_FQ_KERNEL", "1")
    np.testing.assert_allclose(np.asarray(fake_quant_act(x, 4)),
                               np.asarray(ref_out), atol=1e-5)
    np.testing.assert_allclose(np.asarray(fake_quant_act(x, 32)),
                               np.asarray(ref32), rtol=1e-6)
    w = jax.random.normal(jax.random.PRNGKey(6), (32, 8))
    g = jax.grad(lambda t: jnp.sum(fake_quant_weight(t, 4)))(w)
    assert float(jnp.mean(jnp.abs(g - 1.0))) < 0.15   # STE survives


def test_kernel_route_layout_guard(monkeypatch):
    """Non-channel-last reductions and 1-D inputs never route to the
    kernel, even when forced on."""
    from repro.core.quantization import _kernel_route
    monkeypatch.setenv("GALEN_FQ_KERNEL", "1")
    x2 = jnp.zeros((8, 4))
    assert _kernel_route(x2, (0,))
    assert not _kernel_route(x2, (1,))          # reduce over channels
    assert not _kernel_route(jnp.zeros(8), (0,))
    monkeypatch.setenv("GALEN_FQ_KERNEL", "0")
    assert not _kernel_route(x2, (0,))          # forced off
