"""Paper Fig. 3: per-layer policies found by the three agents (text bars)."""
from __future__ import annotations

import json
import os

from benchmarks.common import lm_search


def render_policy(specs, policy, width: int = 24) -> list[str]:
    lines = []
    for s, c in zip(specs, policy.cmps):
        if s.prunable and s.prune_dim:
            frac = c.keep / s.prune_dim
            bar = "#" * int(frac * width)
            lines.append(f"{s.name:16s} keep={c.keep:5d}/{s.prune_dim:<5d} "
                         f"|{bar:<{width}s}| {c.mode:4s} "
                         f"w{c.w_bits:<2d} a{c.a_bits:<2d}")
        elif s.quantizable:
            lines.append(f"{s.name:16s} {'':34s} {c.mode:4s} "
                         f"w{c.w_bits:<2d} a{c.a_bits:<2d}")
    return lines


def run(c=0.5, verbose=True):
    out = {}
    for m, label in (("p", "pruning"), ("q", "quantization"),
                     ("pq", "joint")):
        search = lm_search(m, c, seed=7)
        res = search.run(verbose=False)
        best = res.best_under_budget(0.05) or res.best
        lines = render_policy(search.specs, best.policy)
        out[label] = {
            "policy_render": lines,
            "accuracy": round(best.accuracy, 4),
            "latency_frac": round(best.latency_s / res.ref_latency_s, 4),
        }
        if verbose:
            print(f"\n[fig3] {label} agent (c={c}) acc={best.accuracy:.3f} "
                  f"lat={out[label]['latency_frac']:.3f}")
            for ln in lines:
                print("   " + ln)
    return out


def main(out="artifacts/bench_fig3.json"):
    rows = run()
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    main()
