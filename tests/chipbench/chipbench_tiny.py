"""Shrink the benchmark's cells to a size a CPU test can run, keeping
each traffic file's path: the configuration's widths and depth, the
validation length and the agent's widths and update counts shrink; the
schedule (episodes per batch, batches per epoch, warm-up) and the
validation rows stay."""
from __future__ import annotations

from types import SimpleNamespace

import jax

from chipbench import harness
from chipbench import run as bench_run

# float32 compute: at these widths the logits of a 128-token vocabulary
# sit close enough for bfloat16 rounding to flip the argmax of some
# positions, which the full-size limits are not set for
LM = {"hidden_size": 64, "intermediate_size": 256, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "vocab_size": 128, "compute_dtype": "float32"}


def tiny_cell(workload: str) -> dict:
    cell = harness.load_cell(workload)
    cfg, tr = cell["config"], cell["traffic"]
    cfg.update(LM)
    cfg["weights"]["branch_out_scale"] = 0.5     # 1/sqrt(2 x 2 layers)
    tr["validation"]["seq"] = 16
    tr["agent"].update(hidden=[32, 24], updates_per_episode=4,
                       batch_size=16)
    tr["check_episodes"] = 4
    return cell


def run_tiny(monkeypatch, workload: str, seed: int = 2 ** 31 + 7,
             controls=(), trace: int = 0) -> dict:
    """One run of the shrunk cell on the CPU: the harness's look for a
    chip and its compile cache are steered off, the rest is the run."""
    monkeypatch.setattr(harness, "require_devices",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    args = SimpleNamespace(workload=workload, seed=seed, seconds=0.3,
                           trace=trace)
    return bench_run.execute(args, tiny_cell(workload), controls=controls)
