"""Paper appendix (Fig. 5): sequential (prune->quant, quant->prune) vs
concurrent joint search at the same effective target rate.

Sequential scheme: first run with c1 = 0.5*(1-c)+c ... the paper uses
c1 = 0.5*(1+c)? — it states c_1 = 0.5·(1-c) with c=0.2 interpreted as a
*less aggressive* first stage (0.6 in Fig. 5a/b captions, i.e.
c1 = 1 - 0.5*(1-c)). We follow the figure captions: c1=0.6 then the
second search must reach the remaining factor c/c1."""
from __future__ import annotations

import copy
import json
import os

import jax.numpy as jnp

from benchmarks.common import lm_search
from repro.core.policy import stack_policies


def _frozen_steps(search, frozen_policy, frozen_methods):
    """Apply a previous policy's CMPs as the starting reference so the
    second-stage agent only controls its own method's parameters."""
    search.ref_policy = copy.deepcopy(frozen_policy)
    # the rollout starts every episode from these (keep, w_bits, a_bits)
    pb = stack_policies(search.specs, [search.ref_policy])
    search._ref_rows = tuple(jnp.asarray(x[0], jnp.float32)
                             for x in (pb.keep, pb.w_bits, pb.a_bits))
    # re-derive reference latency from the frozen starting point
    from repro.core.latency import policy_latency
    search.ref_lat_frozen = policy_latency(search.specs, search.ref_policy,
                                           search.hw, search.ctx)
    return search


def sequential(first: str, second: str, c: float, c1: float, seed=4,
               verbose=True):
    s1 = lm_search(first, c1, seed=seed)
    r1 = s1.run(verbose=False)
    best1 = r1.best_under_budget(0.05) or r1.best

    s2 = lm_search(second, c, seed=seed + 1)
    s2 = _frozen_steps(s2, best1.policy, first)
    r2 = s2.run(verbose=False)
    best2 = r2.best_under_budget(0.05) or r2.best
    row = {
        "scheme": f"{first}->{second}",
        "stage1_latency_frac": round(best1.latency_s / r1.ref_latency_s, 4),
        "latency_frac": round(best2.latency_s / r2.ref_latency_s, 4),
        "accuracy": round(best2.accuracy, 4),
        "macs_frac": round(best2.macs_frac, 4),
        "bops": best2.bops,
    }
    if verbose:
        print(f"[fig5] {row['scheme']:8s} final lat={row['latency_frac']:.3f}"
              f" acc={row['accuracy']:.3f}", flush=True)
    return row


def run(c=0.35, c1=0.6, verbose=True):
    rows = [sequential("p", "q", c, c1, verbose=verbose),
            sequential("q", "p", c, c1, verbose=verbose)]
    sj = lm_search("pq", c, seed=6)
    rj = sj.run(verbose=False)
    bj = rj.best_under_budget(0.05) or rj.best
    rows.append({
        "scheme": "joint",
        "latency_frac": round(bj.latency_s / rj.ref_latency_s, 4),
        "accuracy": round(bj.accuracy, 4),
        "macs_frac": round(bj.macs_frac, 4),
        "bops": bj.bops,
    })
    if verbose:
        print(f"[fig5] joint    final lat={rows[-1]['latency_frac']:.3f}"
              f" acc={rows[-1]['accuracy']:.3f}", flush=True)
    return rows


def main(out="artifacts/bench_fig5.json"):
    rows = run()
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    main()
