"""Paper Fig. 4: vary the target compression rate c and check that each
agent's found policy lands on the latency budget (reward-only control)."""
from __future__ import annotations

import json
import os

from benchmarks.common import FULL, lm_search

CS_FULL = (0.25, 0.3, 0.4, 0.5, 0.6, 0.7)
CS_FAST = (0.3, 0.5, 0.7)


def run(cs=None, agents=("p", "q", "pq"), verbose=True):
    cs = cs or (CS_FULL if FULL else CS_FAST)
    rows = []
    labels = {"p": "pruning", "q": "quantization", "pq": "joint"}
    for c in cs:
        for m in agents:
            search = lm_search(m, c, seed=2)
            res = search.run(verbose=False)
            best = res.best_under_budget(0.05) or res.best
            rows.append({
                "table": "fig4", "agent": labels[m], "c": c,
                "achieved_latency_frac": round(
                    best.latency_s / res.ref_latency_s, 4),
                "on_budget": bool(best.latency_ratio <= 1.05),
                "accuracy": round(best.accuracy, 4),
                "ref_accuracy": round(res.ref_accuracy, 4),
            })
            if verbose:
                r = rows[-1]
                print(f"[fig4] {labels[m]:12s} c={c:.2f} -> achieved "
                      f"{r['achieved_latency_frac']:.3f} "
                      f"acc={r['accuracy']:.3f} on_budget={r['on_budget']}",
                      flush=True)
    return rows


def main(out="artifacts/bench_fig4.json"):
    rows = run()
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    main()
