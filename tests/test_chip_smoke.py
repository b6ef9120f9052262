"""chip_smoke.py's phases at the Qwen2-0.5B smoke widths on the CPU: the
same calls and checks the chip run makes, with Pallas interpreted."""
import importlib.util
import os
import sys

import pytest

from repro.models.registry import get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_path(smoke):
    sizes = smoke.Sizes(search_k=2, epoch_batches=3, epochs=3, val_seq=16,
                        serve_steps=4, serve_requests=2)
    out = smoke.one_chip(get_config("qwen2-0.5b", smoke=True), sizes,
                         seed=0)
    assert out["episodes"] == 18
    assert all(t > 0 for t in out["measured_s"])


def test_fleet_parity_path(smoke):
    # 14 batches: the tiny model pushes 10 transitions per episode, so the
    # replay reaches the DDPG batch size only after the warmup's 10
    sizes = smoke.Sizes(val_seq=16, fleet_batches=14)
    out = smoke.fleet_parity(get_config("qwen2-0.5b", smoke=True), sizes,
                             seed=0, data=1)
    assert out["max_diff"] == 0.0


def test_refuses_cpu(smoke, capsys):
    with pytest.raises(SystemExit, match="no TPU chip found"):
        smoke.main([])
