"""Ahead-of-time compiles of the main path's Pallas kernels, at
Qwen2-0.5B widths and the DDPG paper sizes, for a TPU v5e that is
described, not attached. The TPU compiler refuses block shapes and VMEM
use that interpret mode accepts, so these run on the CPU host.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and it keeps it
until it exits. All such compiles live in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.ddpg import _mlp_init
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # noqa: BLE001 — no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_tpu(one_chip, monkeypatch):
    """``compile(fn, *shapes)`` for the described chip, with the
    ``kernels.ops`` wrappers steered onto their TPU (Mosaic) route."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()            # no trace made with interpret=True

    def compile_(fn, *shapes):
        args = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), shapes)
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    yield compile_
    jax.clear_caches()            # nor one made for the chip
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _s(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _mlp_shapes(dims):
    return jax.eval_shape(lambda: _mlp_init(jax.random.PRNGKey(0), dims))


# weights (d_model x d_model, d_model x d_ff, d_ff x d_model), an
# activation slab taller than one row block, and the tied embedding
@pytest.mark.parametrize("shape", [(896, 896), (896, 4864), (4864, 896),
                                   (8192, 896), (151936, 896)])
def test_fake_quant_compiles(compile_tpu, shape):
    compile_tpu(ops.fused_fake_quant, _s(shape), _s((), jnp.int32))


def test_fake_quant_vmapped_bits_compiles(compile_tpu):
    """The search vmaps the bit width over K policies of one weight."""
    compile_tpu(lambda x, b: jax.vmap(
        lambda bb: ops.fused_fake_quant(x, bb))(b),
        _s((896, 4864)), _s((4,), jnp.int32))


@pytest.mark.parametrize("dims,final", [((16, 400, 300, 1), "sigmoid"),
                                        ((17, 400, 300, 1), "linear")])
def test_mlp3_compiles(compile_tpu, dims, final):
    compile_tpu(lambda p, x: ops.fused_mlp3(p, x, final=final),
                _mlp_shapes(dims), _s((128, dims[0])))


# actor 127,401 params = 996 rows of 128; critic 127,801 = 999 rows
@pytest.mark.parametrize("dims", [(16, 400, 300, 1), (17, 400, 300, 1)])
def test_polyak_compiles(compile_tpu, dims):
    p = _mlp_shapes(dims)
    compile_tpu(lambda t, o: ops.fused_polyak(t, o, 0.01), p, p)
