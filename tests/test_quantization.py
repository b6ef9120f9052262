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


# ------------------------------------- gather-first embedding fake quant

# 2**7 * 67 rows: no multiple-of-8 divisor in (128, 512], as Qwen2's
# 151,936 = 2**7 * 1187 has none, so the kernel's range pass takes
# 128-row blocks; 200 channels pad to 256 lanes
_TABLE_ROWS, _TABLE_D = 128 * 67, 200
_ROWS_BITS = [1, 2, 3, 4, 6, 8, 32]
# repeated and out-of-order ids, the first and the last row among them
_TOKENS = np.array([[7, 3, _TABLE_ROWS - 1, 3, 0, 411],
                    [42, 42, 1, 8000, 7, 7]], np.int32)


def _embed_table():
    """Rows of log-uniform norms (3-30, as the benchmark shapes Qwen2's
    table), channel 0 constant at zero, one denormal entry."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    t = jax.random.normal(k1, (_TABLE_ROWS, _TABLE_D))
    norms = jnp.exp(jax.random.uniform(k2, (_TABLE_ROWS, 1),
                                       minval=np.log(3.0),
                                       maxval=np.log(30.0)))
    t = t / jnp.linalg.norm(t, axis=1, keepdims=True) * norms
    return t.at[:, 0].set(0.0).at[3, 5].set(1e-40)


def _take_of_fake_quant(table, idx, bits, dtype=None):
    """The whole-table formulation fake_quant_rows replaces."""
    return jnp.take(fake_quant_weight(table, bits, dtype), idx, axis=0)


def _assert_bitwise(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype
    u = {2: np.uint16, 4: np.uint32}[a.dtype.itemsize]
    np.testing.assert_array_equal(a.view(u), b.view(u))


@pytest.fixture(scope="module")
def embed_table():
    return _embed_table()


@pytest.mark.parametrize("route", ["0", "1"])
@pytest.mark.parametrize("bits", _ROWS_BITS)
def test_fake_quant_rows_equals_take_of_fake_quant(embed_table, route, bits,
                                                   monkeypatch):
    """Gather-first equals quantize-the-table-then-take, bit for bit, on
    the reference route and on the (interpreted) kernel route."""
    from repro.core.quantization import fake_quant_rows
    monkeypatch.setenv("GALEN_FQ_KERNEL", route)
    idx = jnp.asarray(_TOKENS)
    got = jax.jit(fake_quant_rows)(embed_table, idx, jnp.int32(bits))
    want = jax.jit(_take_of_fake_quant)(embed_table, idx, jnp.int32(bits))
    _assert_bitwise(got, want)
    if bits == 32:    # a select, so the denormal survives
        _assert_bitwise(got, jnp.take(embed_table, idx, axis=0))
        assert np.asarray(got)[0, 1, 5] != 0.0
    else:
        assert not np.array_equal(np.asarray(got),
                                  np.asarray(jnp.take(embed_table, idx,
                                                      axis=0)))


@pytest.mark.parametrize("route", ["0", "1"])
def test_fake_quant_rows_vmapped_bits(embed_table, route, monkeypatch):
    """Under vmap over K=4 bit widths with shared tokens, as validation
    runs it."""
    from repro.core.quantization import fake_quant_rows
    monkeypatch.setenv("GALEN_FQ_KERNEL", route)
    idx = jnp.asarray(_TOKENS)
    bits = jnp.array([1, 3, 8, 32], jnp.int32)
    got = jax.jit(jax.vmap(lambda b: fake_quant_rows(embed_table, idx, b)))(
        bits)
    want = jax.jit(jax.vmap(
        lambda b: _take_of_fake_quant(embed_table, idx, b)))(bits)
    assert got.shape == (4,) + _TOKENS.shape + (_TABLE_D,)
    _assert_bitwise(got, want)


@pytest.mark.parametrize("route", ["0", "1"])
@pytest.mark.parametrize("bits", [2, 32])
def test_fake_quant_rows_gradient(embed_table, route, bits, monkeypatch):
    """The table's gradient is the old formulation's: identity through
    the taken rows, then take's scatter-add (repeated ids accumulate);
    the range takes none."""
    from repro.core.quantization import fake_quant_rows
    monkeypatch.setenv("GALEN_FQ_KERNEL", route)
    idx = jnp.asarray(_TOKENS)
    cot = jax.random.normal(jax.random.PRNGKey(12),
                            _TOKENS.shape + (_TABLE_D,))

    def grad_of(f):
        return jax.jit(jax.grad(
            lambda t: jnp.sum(f(t, idx, jnp.int32(bits)) * cot)))(
                embed_table)

    got = grad_of(fake_quant_rows)
    _assert_bitwise(got, grad_of(_take_of_fake_quant))
    np.testing.assert_array_equal(np.asarray(got[42]),
                                  np.asarray(cot[1, 0] + cot[1, 1]))


def _tiny_tied_lm():
    from repro.configs.base import ArchConfig
    from repro.core.compress import CompressibleLM
    from repro.models import model as M

    cfg = ArchConfig(name="tied", num_layers=2, d_model=64, num_heads=4,
                     num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=320,
                     scan_layers=True, tie_embeddings=True)
    return CompressibleLM(cfg, M.init(cfg, jax.random.PRNGKey(0)))


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("route", ["0", "1"])
def test_validation_quantizes_only_embedding_rows(route, monkeypatch):
    """The vmapped validation forward of a tied-embedding LM makes no
    [K, V, d] fake-quantized table, marks the lookup with one
    ``embed_rows`` scope, and gives the whole-table formulation's logits
    exactly."""
    from repro.core.policy import Policy, map_actions, stack_policies
    from repro.models import layers as L
    monkeypatch.setenv("GALEN_FQ_KERNEL", route)
    cm = _tiny_tied_lm()
    cfg, K = cm.cfg, 4
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                          0, cfg.vocab_size)}
    rng = np.random.default_rng(5)
    pb = stack_policies(cm.specs, [
        Policy([map_actions(s, rng.random(3), "pq") for s in cm.specs])
        for _ in range(K)])
    pols = tuple(jnp.asarray(a, jnp.int32)
                 for a in (pb.keep, pb.w_bits, pb.a_bits))
    build = cm.cspec_builder()
    logits = jax.vmap(
        lambda p, k, w, a: cm.logits(batch, build(k, w, a), p),
        in_axes=(None, 0, 0, 0))

    jaxpr = jax.make_jaxpr(logits)(cm.params, *pols).jaxpr
    table = (K, cfg.vocab_size, cfg.d_model)
    assert not any(getattr(v.aval, "shape", None) == table
                   for e in _eqns(jaxpr) for v in e.outvars)
    scopes = {str(e.source_info.name_stack).split("embed_rows")[0]
              for e in _eqns(jaxpr)
              if "embed_rows" in str(e.source_info.name_stack)}
    assert len(scopes) == 1 and "fake_quant" in scopes.pop()

    got = jax.jit(logits)(cm.params, *pols)
    monkeypatch.setattr(L, "fq_rows", _take_of_fake_quant)
    want = jax.jit(logits)(cm.params, *pols)
    _assert_bitwise(got, want)


# ------------------------------ fake quant in the consumer's dtype

_BITS = [1, 2, 4, 6, 8, 32]


@pytest.mark.parametrize("dout", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("din", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bits", _BITS)
def test_fake_quant_in_consumer_dtype_is_one_cast(bits, din, dout,
                                                  monkeypatch):
    """``fake_quant(x, bits, dtype=d)`` is the input-dtype result cast to
    d, bit for bit, on the (interpreted) kernel route and the reference
    route alike: the straight-through value rounded once."""
    from repro.core.quantization import fake_quant_act
    x = (jax.random.normal(jax.random.PRNGKey(bits), (3, 40, 200)) * 3.0
         ).astype(din)
    b = jnp.int32(bits)
    outs = {}
    for route in ("0", "1"):
        monkeypatch.setenv("GALEN_FQ_KERNEL", route)
        got = jax.jit(fake_quant_act, static_argnums=2)(x, b, dout)
        assert got.dtype == dout
        outs[route] = got
        if jnp.dtype(din).itemsize >= jnp.dtype(dout).itemsize:
            _assert_bitwise(got, jax.jit(fake_quant_act)(x, b).astype(dout))
    _assert_bitwise(outs["1"], outs["0"])


@pytest.mark.parametrize("route", ["0", "1"])
@pytest.mark.parametrize("bits", [2, 32])
def test_fake_quant_weight_bf16_gradient_is_identity(route, bits,
                                                     monkeypatch):
    """Through ``fake_quant_weight(w, bits, dtype=bf16)`` the f32 weight's
    gradient is the cotangent itself (rounded to the bf16 the forward
    emits): the straight-through estimator on both routes."""
    monkeypatch.setenv("GALEN_FQ_KERNEL", route)
    w = jax.random.normal(jax.random.PRNGKey(3), (64, 200))
    cot = jax.random.normal(jax.random.PRNGKey(4), (64, 200))
    g = jax.jit(jax.grad(lambda t: jnp.sum(
        fake_quant_weight(t, jnp.int32(bits), jnp.bfloat16)
        .astype(jnp.float32) * cot)))(w)
    _assert_bitwise(g, cot.astype(jnp.bfloat16).astype(jnp.float32))


def _f32_operand_fake_quant(x, bits, dtype=None):
    """Fake quant as validation ran it when the apply pass wrote f32: the
    quantize-dequantize and straight-through select in f32, cast back to
    x's own dtype whatever the consumer reads (which casts again)."""
    from repro.core.quantization import (_minmax, _reference_fq,
                                         _straight_through)
    with jax.named_scope("fake_quant"):
        xf = x.astype(jnp.float32)
        xq = _reference_fq(xf, bits, *_minmax(xf, tuple(range(x.ndim - 1))))
        return _straight_through(xf, xq, bits).astype(x.dtype)


def _hybrid_smoke_lm():
    from repro.configs.granite_4_0_h_micro import SMOKE
    from repro.core.compress import CompressibleLM
    from repro.models import model as M
    return CompressibleLM(SMOKE, M.init(SMOKE, jax.random.PRNGKey(0)))


def _vmapped_validation(cm, K=4, seq=16):
    """``(logits_fn, args)``: the validation forward vmapped over K
    policies' bit widths and keeps with the weights shared, as the
    search's validation runs it."""
    from repro.core.policy import Policy, map_actions, stack_policies
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, seq),
                                          0, cm.cfg.vocab_size)}
    rng = np.random.default_rng(5)
    pb = stack_policies(cm.specs, [
        Policy([map_actions(s, rng.random(3), "pq") for s in cm.specs])
        for _ in range(K)])
    pols = tuple(jnp.asarray(a, jnp.int32)
                 for a in (pb.keep, pb.w_bits, pb.a_bits))
    build = cm.cspec_builder()
    fn = jax.vmap(lambda p, k, w, a: cm.logits(batch, build(k, w, a), p),
                  in_axes=(None, 0, 0, 0))
    return fn, (cm.params,) + pols


def _f32_weight_copies(jaxpr, params, K):
    """Outputs under the ``fake_quant`` scope that are f32 [K, in, out]
    copies of a weight, for K policies."""
    mats = {leaf.shape[-2:] for leaf in jax.tree.leaves(params)
            if leaf.ndim >= 2}
    mats |= {m[::-1] for m in mats}
    return [v.aval for e in _eqns(jaxpr)
            if "fake_quant" in str(e.source_info.name_stack)
            for v in e.outvars
            if getattr(v.aval, "dtype", None) == jnp.float32
            and v.aval.shape[:1] == (K,) and v.aval.shape[1:] in mats]


@pytest.mark.parametrize("build_lm", [_tiny_tied_lm, _hybrid_smoke_lm],
                         ids=["tied", "hybrid"])
def test_validation_fake_quant_emits_matmul_operand(build_lm, monkeypatch):
    """On the kernel route, every apply pass of the vmapped validation
    writes the compute dtype, no f32 [K, in, out] copy of a weight is
    made, and the logits are the f32-operand formulation's, bit for
    bit."""
    from repro.models import layers as L
    monkeypatch.setenv("GALEN_FQ_KERNEL", "1")
    jax.clear_caches()      # no trace made on another route is reused
    cm, K = build_lm(), 4
    compute = L.dtype_of(cm.cfg.compute_dtype)
    logits, args = _vmapped_validation(cm, K)

    jaxpr = jax.make_jaxpr(logits)(*args).jaxpr
    applies = [e for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"
               and e.params["name"] == "fake_quant_apply"]
    assert applies and all(v.aval.dtype == compute
                           for e in applies for v in e.outvars)
    assert not _f32_weight_copies(jaxpr, cm.params, K)

    got = jax.jit(logits)(*args)
    for name in ("fake_quant_act", "fake_quant_weight", "fq_act",
                 "fq_weight"):
        monkeypatch.setattr(L, name, _f32_operand_fake_quant)
    monkeypatch.setattr(L, "fq_rows", lambda t, i, b, dtype=None: jnp.take(
        _f32_operand_fake_quant(t, b), i, axis=0))
    jax.clear_caches()      # the layer scan's body was traced unpatched
    old = jax.make_jaxpr(logits)(*args).jaxpr
    assert _f32_weight_copies(old, cm.params, K)    # what the check finds
    _assert_bitwise(got, jax.jit(logits)(*args))
