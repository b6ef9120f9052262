"""Each benchmark cell, shrunk, end to end on the CPU: the traffic file
drives the program's search engine, the window produces episodes, and
the comparison with the plain references comes out correct."""
from __future__ import annotations

import json
import math

import pytest

from chipbench import harness

import chipbench_tiny

CELLS = [w["name"] for w in harness.load_json(
    harness.os.path.join(harness.ROOT, "BENCHMARK.json"))["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct(monkeypatch, workload):
    out = chipbench_tiny.run_tiny(monkeypatch, workload)
    res, rec = out["result"], out["record"]
    assert res["correct"], out["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    bench = harness.load_cell(workload)["bench"]
    listed = {m["name"] for m in harness.metrics_for(bench, workload,
                                                     "end_to_end")}
    assert set(res["metrics"]) == listed
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(json.loads(json.dumps(res))) == list(res)
    for v in rec["numbers"].values():
        assert math.isfinite(v)
