"""The benchmark's command refuses to measure without the chips a cell
asks for: it exits non-zero and prints no result line."""
from __future__ import annotations

import os
import subprocess
import sys

from chipbench import harness


def test_command_refuses_without_an_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "chipbench", "run.py"),
         "--workload", "qwen2-0.5b.search-pq", "--seed",
         str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "accelerator" in proc.stderr


def test_every_cell_finds_its_files_by_name():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell["limits"]["limits"]
        reported = {m["name"] for m in harness.metrics_for(
            bench, w["name"], "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        layer = harness.metrics_for(bench, w["name"], "per_layer")
        assert layer and all(m["moves"] in reported for m in layer)
        for m in layer:
            assert os.path.exists(os.path.join(
                harness.HERE, "metrics", m["name"] + ".py"))
