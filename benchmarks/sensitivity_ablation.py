"""Paper Fig. 6 (sensitivity curves) + Table 2 / Fig. 7 (ablation:
joint search with the sensitivity features disabled)."""
from __future__ import annotations

import json
import os

import numpy as np

from benchmarks.common import get_lm_testbed, lm_search
from repro.core.compress import CompressibleLM
from repro.core.sensitivity import full_sweep


def sensitivity_curves(verbose=True):
    """Fig. 6: KL distortion per layer for quant-w / quant-a / prune."""
    cfg, params, val, _ = get_lm_testbed()
    cm = CompressibleLM(cfg, params)
    rows = full_sweep(cm, val, w_bits=(8, 4, 2), a_bits=(8, 4, 2),
                      n_prune=5)
    if verbose:
        # later layers should be more sensitive on average (paper Fig. 6)
        by_layer = {}
        for r in rows:
            if r["method"] == "quant_w" and r["param"] == 2:
                by_layer[r["layer"]] = r["kl"]
        print("[fig6] per-layer KL at w=2bit:",
              {k: round(v, 3) for k, v in list(by_layer.items())[:8]},
              flush=True)
    return rows


def ablation(c=0.35, verbose=True):
    """Table 2: joint search, sensitivity enabled vs disabled."""
    out = []
    for enabled in (True, False):
        search = lm_search("pq", c, seed=3, sens_enabled=enabled)
        res = search.run(verbose=False)
        best = res.best_under_budget(0.05) or res.best
        # action heterogeneity: std of kept-fractions + bits across layers
        keeps, bits = [], []
        for s, cmp in zip(search.specs, best.policy.cmps):
            if s.prunable and s.prune_dim:
                keeps.append(cmp.keep / s.prune_dim)
            if s.quantizable:
                bits.append(cmp.w_bits)
        out.append({
            "table": "table2", "sensitivity": enabled,
            "accuracy": round(best.accuracy, 4),
            "macs_frac": round(best.macs_frac, 4),
            "latency_frac": round(best.latency_s / res.ref_latency_s, 4),
            "keep_std": round(float(np.std(keeps)), 4),
            "bits_std": round(float(np.std(bits)), 4),
        })
        if verbose:
            r = out[-1]
            print(f"[table2] sens={enabled}: acc={r['accuracy']:.3f} "
                  f"macs={r['macs_frac']:.3f} keep_std={r['keep_std']:.3f} "
                  f"bits_std={r['bits_std']:.3f}", flush=True)
    return out


def main(out="artifacts/bench_sensitivity.json"):
    rows = {"curves": sensitivity_curves(), "ablation": ablation()}
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    main()
