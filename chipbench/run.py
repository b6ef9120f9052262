"""One run of one benchmark cell.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json`` and its configuration, traffic
and limits files by name, refuses to run without the chips the cell
asks for, sets up, measures for ``--seconds``, checks what the window
produced against the plain references, and prints one JSON line. With
``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` a profiled part of the window gives its per-layer metrics,
the device's busy and traced seconds, and a breakdown.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def execute(args, cell: dict, controls=(), t0: float = None) -> dict:
    """Set up, measure and check one run; returns the driver's record
    and the result line's fields (not yet printed)."""
    import jax
    devs = harness.require_devices(cell["cell"]["chips"])
    harness.enable_cache()
    counter = harness.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    driver = importlib.import_module(
        "chipbench.drivers." + cell["traffic"]["driver"])
    ctx = SimpleNamespace(
        workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), config=cell["config"],
        traffic=cell["traffic"], devices=devs, compiles=counter,
        t0=T0 if t0 is None else t0, controls=tuple(controls))
    rec = driver.run(ctx)
    correct, checks = harness.compare(rec["numbers"], cell["limits"])
    # each control stands in the program's place for the numbers its
    # references compute, and is held to the same limits
    control_checks = {
        name: harness.compare(nums, cell["limits"], names=set(nums))
        for name, nums in rec["controls"].items()}
    result = {"correct": correct, "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": {},
              "device": rec["device"]}
    bench = cell["bench"]
    if not args.trace:
        e2e = {"setup_s": rec["setup_s"], **rec["e2e"]}
        for m in harness.metrics_for(bench, args.workload, "end_to_end"):
            if m["name"] in e2e:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    else:
        from chipbench import costs, trace
        tr = trace.load(rec["trace_dir"])
        result["device"]["busy_s"] = tr.busy_s()
        result["device"]["window_s"] = tr.window_s
        rctx = SimpleNamespace(
            trace=tr, counters=rec["counters"], config=cell["config"],
            traffic=cell["traffic"], costs=costs,
            family=harness.family(cell["config"]),
            peaks=harness.peaks_for(result["device"]["kind"]))
        for m in harness.metrics_for(bench, args.workload, "per_layer"):
            v = harness.read_metric(m["name"], rctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        ops = sorted(tr.op_seconds().items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {"device_ops": [[n, s] for n, s in ops],
                               "idle_gaps": tr.idle_gaps(10)}
    return {"record": rec, "result": result, "checks": checks,
            "control_checks": control_checks}


def main(argv=None):
    args = parse(argv)
    cell = harness.load_cell(args.workload)
    out = execute(args, cell)
    harness.emit(out["result"], out["checks"])


if __name__ == "__main__":
    main()
