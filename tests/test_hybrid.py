"""The Mamba-2 mixer's chunked SSD scan against the step-by-step
recurrence it computes, across several chunks and a ragged tail."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.kernels import ref
from repro.models.blocks import ssd_chunked


@pytest.mark.parametrize("S,chunk", [(70, 16), (50, 16), (9, 16)])
@pytest.mark.parametrize("with_state", [False, True])
def test_ssd_chunked_matches_the_recurrence(S, chunk, with_state):
    B, H, P, N = 2, 3, 8, 5
    ks = jax.random.split(jax.random.PRNGKey(S), 5)
    xh = jax.random.normal(ks[0], (B, S, H, P))
    dA = -jax.random.uniform(ks[1], (B, S, H), maxval=0.4)
    Bm = jax.random.normal(ks[2], (B, S, N))
    Cm = jax.random.normal(ks[3], (B, S, N))
    h0 = jax.random.normal(ks[4], (B, H, P, N)) if with_state else None
    y, fin = ssd_chunked(xh, dA, Bm, Cm, chunk, h0)
    # the recurrence from h0: fold it in as a first step with no input
    if with_state:
        pad = lambda a: np.concatenate([np.zeros_like(a[:, :1]), a], 1)
        Cm0 = np.asarray(Cm)
        yr, fr = ref.ssd_scan_ref(pad(np.asarray(xh)), pad(np.asarray(dA)),
                                  pad(np.asarray(Bm)), pad(Cm0))
        yr = np.asarray(yr)[:, 1:]
        # the padded first step started from zeros; add h0's decayed part
        cum = np.cumsum(np.asarray(dA), 1)                     # [B,S,H]
        y0 = np.einsum("bsn,bhpn,bsh->bshp", Cm0, np.asarray(h0),
                       np.exp(cum))
        yr = yr + y0
        fr = np.asarray(fr) + np.exp(cum[:, -1])[..., None, None] \
            * np.asarray(h0)
    else:
        yr, fr = ref.ssd_scan_ref(xh, dA, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(fr), rtol=2e-4,
                               atol=2e-4)
