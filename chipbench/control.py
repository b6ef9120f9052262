"""The control and the planted faults of a cell, on the chip.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 \
        --seconds 3 [--faults state_unchanged,half_batch,answer_altered]

For each seed, one run of the cell (set-up and a short window at the
cell's own sizes) gives the program's compared numbers and the
control's: the plain reference one step below the configuration's
precision, put in the program's place and held to the cell's limits
(its ``correct`` has to come out false). Each fault named is then
planted in the program and the run made again on the first three
seeds. One JSON line per run goes to standard output; the benchmark's
own runs never run this.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import faults, harness  # noqa: E402
from chipbench import run as bench_run  # noqa: E402


def one(workload, cell, seed, seconds, controls=(), fault=None) -> dict:
    args = SimpleNamespace(workload=workload, seed=seed, seconds=seconds,
                           trace=0)
    undo = faults.plant(fault) if fault else None
    try:
        out = bench_run.execute(args, cell, controls=controls,
                                t0=time.perf_counter())
    finally:
        if undo:
            undo()
    rec = out["record"]
    row = {"workload": workload, "seed": seed, "fault": fault,
           "correct": out["result"]["correct"], "program": out["checks"],
           "search_episodes_per_s": rec["e2e"]["search_episodes_per_s"],
           "setup_s": rec["setup_s"],
           "memory_peak_bytes": rec["device"]["memory_peak_bytes"]}
    for name, (ok, checks) in out["control_checks"].items():
        row[name] = {"correct": ok, "checks": checks}
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        print(json.dumps(one(args.workload, cell, seed, args.seconds,
                             controls=("control",))), flush=True)
    for fault in filter(None, args.faults.split(",")):
        for seed in seeds[:3]:
            print(json.dumps(one(args.workload, cell, seed, args.seconds,
                                 fault=fault)), flush=True)


if __name__ == "__main__":
    main()
