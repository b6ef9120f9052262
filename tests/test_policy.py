"""Property tests for the continuous->discrete policy mapping (Eq. 4/8)."""
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import constraints
from repro.core.policy import (T_INT8, T_MIX, Policy, d_inverse, map_actions,
                               prune_keep_from_action, quant_cmp_from_actions,
                               scale_mix_action)
from repro.core.spec import LayerCMP, LayerSpec


def spec(prune_dim=512, gran=128, in_dim=512, mix=True, prunable=True):
    return LayerSpec(name="u", kind="mlp_up", layer_idx=0, in_dim=in_dim,
                     out_dim=prune_dim, prunable=prunable,
                     prune_dim=prune_dim, prune_granularity=gran,
                     quantizable=True, mix_supported=mix,
                     flops_per_token=1.0, weight_elems=in_dim * prune_dim,
                     act_elems_per_token=in_dim)


@given(st.floats(0, 1), st.integers(1, 4096))
def test_d_inverse_bounds(r, v):
    out = d_inverse(r, v)
    assert 1 <= out <= v + 1
    assert d_inverse(1.0, v) == 1            # max compression -> 1 unit


@given(st.floats(0, 1), st.floats(0, 1), st.integers(8, 2048))
def test_d_inverse_monotone(r1, r2, v):
    lo, hi = min(r1, r2), max(r1, r2)
    assert d_inverse(hi, v) <= d_inverse(lo, v)


@given(st.floats(0, 1), st.floats(0, 1))
def test_quant_mode_thresholds(aw, aa):
    cmp = quant_cmp_from_actions(aw, aa)
    if max(aw, aa) > T_MIX:
        assert cmp.mode == "MIX"
        assert 1 <= cmp.w_bits <= 6 and 1 <= cmp.a_bits <= 6
    elif max(aw, aa) > T_INT8:
        assert cmp.mode == "INT8" and cmp.w_bits == 8
    else:
        assert cmp.mode == "FP32" and cmp.w_bits == 32


def test_mix_extremes():
    # action just above threshold -> weakest MIX (6 bits); action 1 -> 1 bit
    assert quant_cmp_from_actions(0.5001, 0.0).w_bits == 6
    assert quant_cmp_from_actions(1.0, 1.0).w_bits == 1
    assert scale_mix_action(0.5) == 0.0
    assert scale_mix_action(1.0) == 1.0


@given(st.floats(0, 1))
def test_legalize_granularity(a):
    s = spec(prune_dim=512, gran=128)
    cmp = map_actions(s, [a, 0.0, 0.0], "pq")
    assert cmp.keep % 128 == 0
    assert 128 <= cmp.keep <= 512


def test_legalize_mix_fallback():
    # in_dim not 256-aligned and not conv -> MIX illegal -> INT8
    s = spec(in_dim=100)
    cmp = map_actions(s, [0.0, 0.9, 0.9], "pq")
    assert cmp.mode == "INT8"


def test_non_prunable_keeps_all():
    s = spec(prunable=False)
    cmp = map_actions(s, [1.0], "p")
    assert cmp.keep == s.prune_dim


def test_policy_macs_bops():
    specs = [spec(), spec()]
    ref = Policy.reference(specs)
    assert ref.macs_fraction(specs) == pytest.approx(1.0)
    half = Policy([LayerCMP(keep=256), LayerCMP(keep=512)])
    assert half.macs_fraction(specs) == pytest.approx(0.75)
    # BOPs: int8 policy is 16x fewer BOPs than fp32
    p32 = Policy([LayerCMP(keep=512, mode="FP32", w_bits=32, a_bits=32)] * 2)
    p8 = Policy([LayerCMP(keep=512, mode="INT8", w_bits=8, a_bits=8)] * 2)
    assert p32.bops(specs) / p8.bops(specs) == pytest.approx(16.0)
