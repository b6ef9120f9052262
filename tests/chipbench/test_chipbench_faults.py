"""A run with the timed path broken underneath comes out not correct:
one case per fault the search cell can have (a state left unchanged,
half the batch left out, an answer altered where it is produced), and
the control (the references one precision step down, put in the
program's place) is not correct against the same limits."""
from __future__ import annotations

import pytest

from chipbench import faults

import chipbench_tiny

WORKLOAD = "qwen2-0.5b.search-pq"


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fault_is_not_correct(monkeypatch, fault):
    undo = faults.plant(fault)
    try:
        out = chipbench_tiny.run_tiny(monkeypatch, WORKLOAD)
    finally:
        undo()
    assert not out["result"]["correct"], out["checks"]


def test_control_is_not_correct(monkeypatch):
    out = chipbench_tiny.run_tiny(monkeypatch, WORKLOAD,
                                  controls=("control",))
    assert out["result"]["correct"], out["checks"]
    ok, checks = out["control_checks"]["control"]
    program_only = {"illegal_units", "window_compiles"}
    assert set(checks) == set(out["checks"]) - program_only
    assert not ok, checks
