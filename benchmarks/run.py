"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines per the harness contract,
then emits each table's rows. Fast subset by default; set
``GALEN_BENCH_FULL=1`` for paper-scale episode counts and the complete
sweeps (hours on one CPU core).

  table1  — agent comparison (paper Table 1)
  fig4    — target-rate sweep (paper Fig. 4)
  fig3    — policy analysis (paper Fig. 3)
  table2  — sensitivity ablation (paper Tab. 2 / Fig. 6-7)
  fig5    — sequential vs joint (paper App. A)         [FULL only]
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

FULL = os.environ.get("GALEN_BENCH_FULL", "0") == "1"


def _stage(name, fn):
    t0 = time.time()
    out = fn()
    us = (time.time() - t0) * 1e6
    n = len(out) if hasattr(out, "__len__") else 1
    print(f"{name},{us:.0f},rows={n}", flush=True)
    return out


def main() -> None:
    from benchmarks import (agent_comparison, policy_analysis, rate_sweep,
                            sensitivity_ablation)

    print("name,us_per_call,derived")
    _stage("bench.table1_agent_comparison", lambda: agent_comparison.main())
    _stage("bench.fig4_rate_sweep", lambda: rate_sweep.main())
    _stage("bench.fig3_policy_analysis", lambda: policy_analysis.main())
    _stage("bench.table2_sensitivity", lambda: sensitivity_ablation.main())
    if FULL:
        from benchmarks import resnet_table1, sequential_vs_joint
        _stage("bench.fig5_sequential_vs_joint",
               lambda: sequential_vs_joint.main())
        _stage("bench.resnet_table1", lambda: resnet_table1.main())
    print("bench.done,0,ok")


if __name__ == "__main__":
    main()
