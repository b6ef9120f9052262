"""Reward function tests (paper Eq. 6)."""
import numpy as np
import pytest

from hypothesis import given
from hypothesis import strategies as st

from repro.core.reward import (RewardConfig, absolute_reward,
                               compute_reward, compute_reward_batch,
                               hard_exponential_reward)


def test_max_at_target():
    """Reward is maximized exactly at T = c * T_ref."""
    base = absolute_reward(0.9, 30.0, 100.0, 0.3)
    assert base == pytest.approx(0.9)
    assert absolute_reward(0.9, 40.0, 100.0, 0.3) < base
    assert absolute_reward(0.9, 20.0, 100.0, 0.3) < base  # undershoot
    # penalized too (paper: "although the used reward also penalizes these")


@given(st.floats(0.01, 1.0), st.floats(1.0, 100.0))
def test_penalty_symmetric_in_ratio(c, t_ref):
    over = absolute_reward(0.5, c * t_ref * 1.2, t_ref, c)
    under = absolute_reward(0.5, c * t_ref * 0.8, t_ref, c)
    assert over == pytest.approx(under, rel=1e-6)


def test_beta_scales_penalty():
    r1 = absolute_reward(0.5, 60.0, 100.0, 0.3, beta=-1.0)
    r3 = absolute_reward(0.5, 60.0, 100.0, 0.3, beta=-3.0)
    assert (0.5 - r3) == pytest.approx(3 * (0.5 - r1))


def test_hard_exponential_only_penalizes_overshoot():
    assert hard_exponential_reward(0.9, 20.0, 100.0, 0.3) == 0.9
    assert hard_exponential_reward(0.9, 40.0, 100.0, 0.3) < 0.9


def test_dispatch():
    cfg = RewardConfig(target_ratio=0.5, beta=-2.0)
    assert compute_reward(cfg, 1.0, 50.0, 100.0) == pytest.approx(1.0)


def test_dispatch_absolute_uses_beta():
    """compute_reward must thread cfg.beta into the absolute reward."""
    cfg = RewardConfig(target_ratio=0.3, beta=-7.0)
    assert compute_reward(cfg, 0.9, 60.0, 100.0) == pytest.approx(
        absolute_reward(0.9, 60.0, 100.0, 0.3, beta=-7.0))


def test_dispatch_hard_exponential_uses_hard_beta():
    """Regression: kind="hard_exponential" used to ignore the config
    and always run with the -0.07 default exponent."""
    cfg = RewardConfig(target_ratio=0.3, kind="hard_exponential",
                       hard_beta=-0.5)
    got = compute_reward(cfg, 0.9, 60.0, 100.0)
    assert got == pytest.approx(
        hard_exponential_reward(0.9, 60.0, 100.0, 0.3, beta=-0.5))
    assert got != pytest.approx(
        hard_exponential_reward(0.9, 60.0, 100.0, 0.3, beta=-0.07))
    # undershoot stays unpenalized regardless of the exponent
    assert compute_reward(cfg, 0.9, 20.0, 100.0) == pytest.approx(0.9)


@pytest.mark.parametrize("kind", ["absolute", "hard_exponential"])
def test_compute_reward_batch_matches_scalar(kind):
    """The jnp batch form (used inside the fused rollout finish path)
    == the scalar host path, both reward kinds."""
    cfg = RewardConfig(target_ratio=0.4, beta=-2.0, kind=kind,
                       hard_beta=-0.11)
    accs = np.linspace(0.1, 0.9, 7)
    lats = np.linspace(20.0, 120.0, 7)
    want = [compute_reward(cfg, a, l, 100.0)
            for a, l in zip(accs, lats)]
    got = np.asarray(compute_reward_batch(
        cfg, accs.astype(np.float32), lats.astype(np.float32), 100.0))
    np.testing.assert_allclose(got, want, rtol=1e-5)
