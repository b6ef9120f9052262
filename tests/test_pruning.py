"""ℓ1 structured pruning tests."""
import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.pruning import (head_scores, keep_mask, keep_mask_dynamic,
                                l1_scores, slice_indices)


@given(st.integers(1, 64), st.integers(0, 64))
@settings(max_examples=40, deadline=None)
def test_keep_mask_count(n, keep):
    scores = jnp.asarray(np.random.default_rng(n).random(n))
    m = keep_mask(scores, keep)
    assert int(jnp.sum(m)) == min(keep, n)
    assert set(np.unique(np.asarray(m))) <= {0.0, 1.0}


@given(st.integers(1, 32), st.integers(0, 40), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=60, deadline=None)
def test_keep_mask_dynamic_matches_static(n, keep, seed):
    """Traced variant selects exactly keep_mask's channels — including
    on tied scores (quantized score draw forces frequent ties)."""
    rng = np.random.default_rng(seed)
    scores = jnp.asarray(np.round(rng.random(n) * 4) / 4)
    static = keep_mask(scores, keep)
    dynamic = keep_mask_dynamic(scores, jnp.int32(keep))
    np.testing.assert_array_equal(np.asarray(static), np.asarray(dynamic))


def test_keep_mask_dynamic_traced():
    scores = jnp.asarray([0.1, 5.0, 0.2, 3.0, 0.05])
    out = jax.jit(keep_mask_dynamic)(scores, jnp.int32(2))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(keep_mask(scores, 2)))


def test_keep_mask_selects_largest():
    scores = jnp.asarray([0.1, 5.0, 0.2, 3.0, 0.05])
    m = np.asarray(keep_mask(scores, 2))
    assert list(np.nonzero(m)[0]) == [1, 3]


def test_keep_mask_ties():
    scores = jnp.ones((8,))
    m = keep_mask(scores, 3)
    assert int(jnp.sum(m)) == 3


def test_l1_scores_group():
    w1 = jnp.asarray([[1.0, -2.0], [0.0, 1.0]])   # col sums of |.|: 1, 3
    w2 = jnp.asarray([[2.0, 0.0], [1.0, 0.0]])    # 3, 0
    s = l1_scores([w1, w2])
    np.testing.assert_allclose(np.asarray(s), [4.0, 3.0])


def test_head_scores():
    d, H, hd = 8, 4, 2
    w = jnp.zeros((d, H * hd)).at[:, 2:4].set(1.0)  # head 1 hot
    s = np.asarray(head_scores(w, H))
    assert s.argmax() == 1
    assert s.shape == (H,)


def test_slice_indices_roundtrip():
    scores = jnp.asarray([3.0, 1.0, 2.0, 0.5])
    m = keep_mask(scores, 2)
    idx = slice_indices(m)
    assert list(idx) == [0, 2]
