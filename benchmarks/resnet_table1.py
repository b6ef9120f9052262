"""Paper Table 1 on the paper's own model family: ResNet (conv channel
pruning + quantization) on the blob-image task — per-image latency on one
v5e chip as the device, mirroring the Raspberry-Pi single-image scenario.

  PYTHONPATH=src:. python -m benchmarks.resnet_table1
"""
from __future__ import annotations

import json
import os

from benchmarks.common import resnet_search


def run(cs=(0.5, 0.35), verbose=True):
    rows = []
    for c in cs:
        for methods, label in (("p", "Pruning Agent"),
                               ("q", "Quantization A."),
                               ("pq", "Joint Agent")):
            search = resnet_search(methods, c, seed=11)
            res = search.run(verbose=False)
            best = res.best_under_budget(0.05) or res.best
            rows.append({
                "table": "resnet_table1", "method": label, "c": c,
                "macs_frac": round(best.macs_frac, 4),
                "latency_frac": round(best.latency_s / res.ref_latency_s, 4),
                "on_budget": bool(best.latency_ratio <= 1.05),
                "accuracy": round(best.accuracy, 4),
                "ref_accuracy": round(res.ref_accuracy, 4),
            })
            if verbose:
                r = rows[-1]
                print(f"[resnet-t1] {label:16s} c={c}: "
                      f"lat={r['latency_frac']:.3f} acc={r['accuracy']:.3f} "
                      f"(clean {r['ref_accuracy']:.3f}) "
                      f"macs={r['macs_frac']:.3f} budget={r['on_budget']}",
                      flush=True)
    return rows


def main(out="artifacts/bench_resnet_table1.json"):
    rows = run()
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    main()
