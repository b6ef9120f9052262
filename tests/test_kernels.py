"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.quant_matmul import quant_matmul


# --------------------------- quant matmul ----------------------------------

@pytest.mark.parametrize("M,K,N", [(64, 128, 64), (200, 300, 130),
                                   (256, 256, 256), (33, 512, 257)])
def test_int8_matmul_matches_int_ref(M, K, N):
    x = jax.random.normal(jax.random.PRNGKey(M), (M, K))
    w = jax.random.normal(jax.random.PRNGKey(N), (K, N))
    y = ops.quantized_matmul(x, w, w_bits=8)
    xq, sx, zx = ref.quantize_rows(x, 8)
    wq, sw, zw = ref.quantize_cols(w, 8)
    yr = ref.int8_matmul_ref(xq, wq, sx, zx, sw, zw)
    # int32 accumulation is exact; the f32 zero-point correction sums can
    # exceed 2^24 so kernel/ref may differ by f32 association noise.
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-3, atol=0.1)


@pytest.mark.parametrize("M,K,N", [(64, 128, 64), (100, 256, 96)])
def test_int8_matmul_close_to_f32(M, K, N):
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K))
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N))
    y = ops.quantized_matmul(x, w, w_bits=8)
    rel = float(jnp.linalg.norm(y - x @ w) / jnp.linalg.norm(x @ w))
    assert rel < 0.03


def test_int4_matmul():
    x = jax.random.normal(jax.random.PRNGKey(2), (128, 256))
    w = jax.random.normal(jax.random.PRNGKey(3), (256, 128))
    y = ops.quantized_matmul(x, w, w_bits=4)
    rel = float(jnp.linalg.norm(y - x @ w) / jnp.linalg.norm(x @ w))
    assert rel < 0.2  # 4-bit weights on gaussian data


def test_int4_pack_unpack_roundtrip():
    w4 = jax.random.randint(jax.random.PRNGKey(4), (64, 32), -8, 8) \
        .astype(jnp.int8)
    assert bool(jnp.all(ref.unpack_int4_ref(ref.pack_int4(w4)) == w4))


def test_quant_matmul_block_shapes():
    """Kernel correct for several BlockSpec tilings."""
    M = K = N = 512
    x = jax.random.normal(jax.random.PRNGKey(5), (M, K))
    w = jax.random.normal(jax.random.PRNGKey(6), (K, N))
    xq, sx, zx = ref.quantize_rows(x, 8)
    wq, sw, zw = ref.quantize_cols(w, 8)
    yr = ref.int8_matmul_ref(xq, wq, sx, zx, sw, zw)
    for bm, bk, bn in [(128, 128, 128), (256, 512, 128), (512, 256, 256)]:
        y = quant_matmul(xq, wq, sx, zx, sw, zw, bm=bm, bk=bk, bn=bn,
                         interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4,
                                   atol=1e-3)


def test_asymmetric_zero_point_convention():
    """Locks the ADD convention x = s·(q + z) end to end: strongly
    shifted (non-zero-mean) data makes the zero-point correction terms
    large, so any sign error in the epilogue is a gross miss. Kernel,
    integer-accumulation ref and dequantize-then-matmul ground truth
    must all agree, and all must approximate the f32 matmul."""
    M, K, N = 64, 128, 96
    x = jax.random.normal(jax.random.PRNGKey(20), (M, K)) + 3.0
    w = jax.random.normal(jax.random.PRNGKey(21), (K, N)) - 1.0
    xq, sx, zx = ref.quantize_rows(x, 8)
    wq, sw, zw = ref.quantize_cols(w, 8)
    want = ref.dequant_matmul_ref(xq, wq, sx, zx, sw, zw)
    got_ref = ref.int8_matmul_ref(xq, wq, sx, zx, sw, zw)
    got_kern = quant_matmul(xq, wq, sx, zx, sw, zw, interpret=True)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                               rtol=1e-3, atol=0.1)
    np.testing.assert_allclose(np.asarray(got_kern), np.asarray(want),
                               rtol=1e-3, atol=0.1)
    fp = x @ w
    rel = float(jnp.linalg.norm(want - fp) / jnp.linalg.norm(fp))
    assert rel < 0.03
    # the test has teeth: SUBTRACT-convention zero points miss badly
    wrong = ref.int8_matmul_ref(xq, wq, sx, -zx, sw, -zw)
    rel_wrong = float(jnp.linalg.norm(wrong - fp) / jnp.linalg.norm(fp))
    assert rel_wrong > 10 * rel


@pytest.mark.parametrize("M,K,N", [(64, 128, 64), (32, 256, 96)])
def test_quant_matmul_packed_matches_ref(M, K, N):
    """packed=True consumes ``ref.pack_int4`` nibbles and must equal the
    dequantize-then-matmul ground truth of the unpacked codes (tight),
    and stay within int4 noise of the f32 matmul (loose)."""
    x = jax.random.normal(jax.random.PRNGKey(M + 40), (M, K))
    w = jax.random.normal(jax.random.PRNGKey(N + 41), (K, N))
    xq, sx, zx = ref.quantize_rows(x, 8)
    wq, sw, zw = ref.quantize_cols(w, 4)
    y = quant_matmul(xq, ref.pack_int4(wq), sx, zx, sw, zw,
                     packed=True, interpret=True)
    want = ref.dequant_matmul_ref(xq, wq, sx, zx, sw, zw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-3, atol=0.1)
    fp = x @ w
    rel = float(jnp.linalg.norm(y - fp) / jnp.linalg.norm(fp))
    assert rel < 0.2


def test_quant_matmul_packed_k_true():
    """Zero-padding K must not corrupt the K·zx·zw zero-point term:
    with ``k_true`` the padded kernel reproduces the unpadded ground
    truth exactly (padded q codes contribute nothing to acc or the
    row/col sums; only the K count needs correcting)."""
    M, K_true, K, N = 32, 300, 512, 64
    x = jax.random.normal(jax.random.PRNGKey(50), (M, K_true)) + 1.0
    w = jax.random.normal(jax.random.PRNGKey(51), (K_true, N))
    xq, sx, zx = ref.quantize_rows(x, 8)
    wq, sw, zw = ref.quantize_cols(w, 4)
    want = ref.dequant_matmul_ref(xq, wq, sx, zx, sw, zw)
    xq_p = jnp.zeros((M, K), jnp.int8).at[:, :K_true].set(xq)
    wq_p = jnp.zeros((K, N), jnp.int8).at[:K_true].set(wq)
    y = quant_matmul(xq_p, ref.pack_int4(wq_p), sx, zx, sw, zw,
                     packed=True, k_true=K_true, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-3, atol=0.1)
    # without the correction the padded run is measurably off
    y_bad = quant_matmul(xq_p, ref.pack_int4(wq_p), sx, zx, sw, zw,
                         packed=True, interpret=True)
    assert float(jnp.max(jnp.abs(y_bad - want))) > 1.0


def test_unpack_variants_agree():
    """The kernel-side, deploy-side and ref unpackers share one nibble
    layout (low nibble = even row) — all three invert ``pack_int4``."""
    from repro.core.deploy import unpack_int4_weight
    from repro.kernels.quant_matmul import unpack_int4
    w4 = jax.random.randint(jax.random.PRNGKey(7), (64, 32), -8, 8) \
        .astype(jnp.int8)
    packed = ref.pack_int4(w4)
    for fn in (unpack_int4, unpack_int4_weight, ref.unpack_int4_ref):
        assert bool(jnp.all(fn(packed) == w4)), fn.__name__


# --------------------------- fake quant ------------------------------------

@pytest.mark.parametrize("shape", [(64, 32), (128, 100), (7, 257)])
@pytest.mark.parametrize("bits", [2, 4, 8, 32])
def test_fake_quant_kernel(shape, bits):
    x = jax.random.normal(jax.random.PRNGKey(bits), shape)
    a = ops.fused_fake_quant(x, bits)
    b = ref.fake_quant_ref(x, bits)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fake_quant_dtypes(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 16)).astype(dtype)
    a = ops.fused_fake_quant(x, 8)
    assert a.dtype == dtype


# --------------------------- flash attention -------------------------------

@pytest.mark.parametrize("S,H,KV,D", [(128, 4, 4, 32), (200, 8, 2, 16),
                                      (512, 4, 1, 64)])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 96)])
def test_flash_attention(S, H, KV, D, causal, window):
    B = 2
    q = jax.random.normal(jax.random.PRNGKey(S), (B, H, S, D))
    k = jax.random.normal(jax.random.PRNGKey(S + 1), (B, KV, S, D))
    v = jax.random.normal(jax.random.PRNGKey(S + 2), (B, KV, S, D))
    a = ops.flash_attention(q, k, v, causal=causal, window=window)
    b = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_flash_attention_bf16():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 128, 32),
                          jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 128, 32),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 128, 32),
                          jnp.bfloat16)
    a = ops.flash_attention(q, k, v)
    b = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=0.04)


# --------------------------- rglru scan ------------------------------------

@pytest.mark.parametrize("B,S,C", [(2, 64, 96), (1, 128, 32), (3, 48, 256)])
def test_rglru_scan(B, S, C):
    a = jax.random.uniform(jax.random.PRNGKey(B), (B, S, C),
                           minval=0.4, maxval=0.99)
    b = jax.random.normal(jax.random.PRNGKey(S), (B, S, C))
    out = ops.rglru_scan(a, b)
    want = ref.rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_rglru_scan_initial_state():
    B, S, C = 2, 32, 64
    a = jax.random.uniform(jax.random.PRNGKey(0), (B, S, C), minval=0.5,
                           maxval=0.95)
    b = jax.random.normal(jax.random.PRNGKey(1), (B, S, C))
    h0 = jax.random.normal(jax.random.PRNGKey(2), (B, C))
    out = ops.rglru_scan(a, b, h0)
    want = ref.rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# --------------------------- ssd scan --------------------------------------

@pytest.mark.parametrize("B,S,H,P,N,chunk", [(2, 64, 4, 16, 8, 16),
                                             (1, 128, 2, 32, 16, 32),
                                             (2, 96, 3, 8, 8, 32)])
def test_ssd_scan(B, S, H, P, N, chunk):
    xh = jax.random.normal(jax.random.PRNGKey(B), (B, S, H, P))
    dA = -jax.random.uniform(jax.random.PRNGKey(S), (B, S, H), maxval=0.5)
    Bm = jax.random.normal(jax.random.PRNGKey(H), (B, S, N))
    Cm = jax.random.normal(jax.random.PRNGKey(P), (B, S, N))
    y, fin = ops.ssd_scan(xh, dA, Bm, Cm, chunk=chunk)
    yr, fr = ref.ssd_scan_ref(xh, dA, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(fr), rtol=2e-4,
                               atol=2e-4)


def test_ssd_matches_model_path():
    """Kernel agrees with the chunked jnp path used inside mamba2 blocks."""
    from repro.models.blocks import ssd_chunked
    B, S, H, P, N = 1, 64, 2, 16, 8
    xh = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, P))
    dA = -jax.random.uniform(jax.random.PRNGKey(1), (B, S, H), maxval=0.3)
    Bm = jax.random.normal(jax.random.PRNGKey(2), (B, S, N))
    Cm = jax.random.normal(jax.random.PRNGKey(3), (B, S, N))
    y_model, f_model = ssd_chunked(xh, dA, Bm, Cm, chunk=16)
    y_kern, f_kern = ops.ssd_scan(xh, dA, Bm, Cm, chunk=16)
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_kern),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(f_model), np.asarray(f_kern),
                               rtol=2e-4, atol=2e-4)


# ----------------------- fused MLP3 + flat Polyak ---------------------------

def _mlp_params(key, dims):
    ks = jax.random.split(key, len(dims) - 1)
    return [{"w": jax.random.normal(k, (a, b)) / jnp.sqrt(a),
             "b": jax.random.normal(jax.random.fold_in(k, 1), (b,)) * 0.1}
            for k, (a, b) in zip(ks, zip(dims[:-1], dims[1:]))]


def _mlp_ref(params, x, sigmoid):
    h = x
    for i, l in enumerate(params):
        h = h @ l["w"] + l["b"]
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return jax.nn.sigmoid(h) if sigmoid else h


@pytest.mark.parametrize("B,dims,final", [
    (16, (10, 400, 300, 6), "sigmoid"),     # paper actor trunk
    (16, (16, 400, 300, 1), "linear"),      # paper critic trunk
    (33, (7, 50, 30, 5), "sigmoid"),        # odd dims exercise padding
    (8, (128, 128, 128, 128), "linear"),    # exactly lane-aligned
])
def test_fused_mlp3_forward_matches_ref(B, dims, final):
    params = _mlp_params(jax.random.PRNGKey(B), dims)
    x = jax.random.normal(jax.random.PRNGKey(B + 1), (B, dims[0]))
    y = ops.fused_mlp3(params, x, final=final)
    yr = _mlp_ref(params, x, final == "sigmoid")
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("final", ["linear", "sigmoid"])
def test_fused_mlp3_backward_matches_ref(final):
    dims = (9, 40, 30, 3)
    params = _mlp_params(jax.random.PRNGKey(7), dims)
    x = jax.random.normal(jax.random.PRNGKey(8), (24, dims[0]))

    def loss_k(p, x):
        return jnp.sum(ops.fused_mlp3(p, x, final=final) ** 2)

    def loss_r(p, x):
        return jnp.sum(_mlp_ref(p, x, final == "sigmoid") ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1))(params, x)
    gr = jax.grad(loss_r, argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_fused_mlp3_under_jit_and_vmap():
    dims = (6, 32, 24, 4)
    params = _mlp_params(jax.random.PRNGKey(9), dims)
    x = jax.random.normal(jax.random.PRNGKey(10), (16, dims[0]))
    y = jax.jit(lambda p, x: ops.fused_mlp3(p, x, final="sigmoid"))(
        params, x)
    yr = _mlp_ref(params, x, True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("sizes", [
    [(400, 300), (300,), (300, 1)],     # lane-unaligned leaves
    [(7,), (13, 5)],                    # total size not a lane multiple
    [(256, 128)],                       # exactly aligned
])
def test_fused_polyak_matches_tree_map(sizes):
    keys = jax.random.split(jax.random.PRNGKey(11), 2 * len(sizes))
    target = [jax.random.normal(keys[2 * i], s)
              for i, s in enumerate(sizes)]
    online = [jax.random.normal(keys[2 * i + 1], s)
              for i, s in enumerate(sizes)]
    tau = 0.01
    out = ops.fused_polyak(target, online, tau)
    ref_out = jax.tree.map(lambda t, p: (1 - tau) * t + tau * p,
                           target, online)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref_out)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_fused_polyak_nested_tree():
    """Dict-of-list params (the ddpg layout) survive the flatten trip."""
    target = [{"w": jnp.ones((5, 3)), "b": jnp.zeros((3,))},
              {"w": jnp.full((3, 2), 2.0), "b": jnp.ones((2,))}]
    online = jax.tree.map(lambda x: x + 1.0, target)
    out = ops.fused_polyak(target, online, 0.5)
    ref_out = jax.tree.map(lambda t, p: 0.5 * t + 0.5 * p, target, online)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref_out)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def _assert_fq_close(got, want, x, bits):
    """Equal to 1e-5 except where ``floor`` sits on a level boundary:
    compiled apart (kernel vs. fused XLA, with or without an FMA), the
    two may land one quantization level apart there. Allow that on at
    most 1e-5 of the elements, and never more than one level."""
    got, want, x = (np.asarray(a, np.float64) for a in (got, want, x))
    diff = np.abs(got - want)
    if bits >= 32:
        assert diff.max() == 0.0
        return
    step = (x.max(0) - x.min(0)) / (2.0 ** bits - 1)
    assert np.all(diff <= step * 1.001 + 1e-5), diff.max()
    assert np.mean(diff > 1e-5) <= 1e-5, np.sum(diff > 1e-5)


# Qwen2-0.5B widths (d_model 896, d_ff 4864), an activation slab taller
# than one row block, and a row count with no aligned divisor (padded)
@pytest.mark.parametrize("shape", [(896, 896), (896, 4864), (4864, 896),
                                   (8192, 896), (1100, 200)])
@pytest.mark.parametrize("bits", [4, 8])
def test_fake_quant_kernel_padded_widths(shape, bits):
    x = jax.random.normal(jax.random.PRNGKey(bits), shape)
    _assert_fq_close(ops.fused_fake_quant(x, bits),
                     ref.fake_quant_ref(x, bits), x, bits)


def test_fake_quant_kernel_vmapped_bits():
    """The search vmaps the bit width over K policies of one weight."""
    x = jax.random.normal(jax.random.PRNGKey(5), (896, 4864))
    bits = jnp.array([2, 4, 8, 32], jnp.int32)
    got = jax.vmap(lambda b: ops.fused_fake_quant(x, b))(bits)
    for i, b in enumerate([2, 4, 8, 32]):
        _assert_fq_close(got[i], ref.fake_quant_ref(x, b), x, b)


@pytest.mark.parametrize("dims", [(16, 400, 300, 1),     # actor: 996 rows
                                  (17, 400, 300, 1)])    # critic: 999 rows
def test_fused_polyak_paper_sizes(dims):
    from repro.core.ddpg import _mlp_init
    target = _mlp_init(jax.random.PRNGKey(12), dims)
    online = _mlp_init(jax.random.PRNGKey(13), dims)
    out = ops.fused_polyak(target, online, 0.01)
    ref_out = jax.tree.map(lambda t, p: 0.99 * t + 0.01 * p, target, online)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref_out)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
