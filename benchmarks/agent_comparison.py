"""Paper Table 1: compressed-model performance per agent at target
compression ratios c (pruning / quantization / joint).

Reports MACs fraction, BOPs, oracle latency ratio, accuracy before and
after a short QAT retrain (the paper retrains 30 epochs).

``engine`` picks how the three agents are searched: "independent" (one
search after another, default), or "population" — the p/q/pq agents
run in lockstep as one ``PopulationSearch`` (action dims padded to the
joint agent's 3)."""
from __future__ import annotations

import json
import os
import time

import jax

from benchmarks.common import EPISODES, lm_search
from repro.core.search import PopulationSearch
from repro.optim.optimizer import OptimizerConfig, adamw_init
from repro.train.train_step import make_train_step


def qat_retrain(search, policy, steps: int = 60):
    """Short QAT retrain of the compressed model (paper: 30 epochs)."""
    cm = search.cmodel
    cs = cm.build_cspec(policy)
    ocfg = OptimizerConfig(lr=1e-3, warmup_steps=5, total_steps=steps,
                           weight_decay=0.0)
    params = cm.params
    opt = adamw_init(params, ocfg)
    step = jax.jit(make_train_step(cm.cfg, ocfg, cspec=cs))
    from repro.data.pipeline import make_bigram_table, sample_bigram
    import jax.numpy as jnp
    table = make_bigram_table(cm.cfg.vocab_size, 0)
    for s in range(steps):
        toks = sample_bigram(table, 16, 48, 777_000 + s)
        params, opt, _ = step(params, opt, {"tokens": jnp.asarray(toks)})
    # evaluate retrained accuracy with the SAME policy cspec
    retrained = type(cm)(cm.cfg, params)
    cs2 = retrained.build_cspec(policy)
    return float(retrained.accuracy(search.val_batch, cs2))


AGENTS = (("p", "Pruning Agent"), ("q", "Quantization A."),
          ("pq", "Joint Agent"))


def _search_trio(c, engine: str):
    """(search, result, elapsed_s) per agent, under the chosen engine."""
    if engine == "independent":
        out = []
        for methods, _label in AGENTS:
            t0 = time.time()
            search = lm_search(methods, c, seed=1)
            res = search.run(verbose=False)
            out.append((search, res, time.time() - t0))
        return out
    if engine == "population":
        # members share one episode count (PopulationSearch runs the
        # population in lockstep); use the trio's maximum so no agent
        # gets a smaller search budget than when searched independently
        episodes = max(EPISODES[m] for m, _label in AGENTS)
        searches = [lm_search(m, c, seed=1, episodes=episodes,
                              action_dim=3)
                    for m, _label in AGENTS]
        t0 = time.time()
        results = PopulationSearch(searches).run(episodes=episodes)
        dt = (time.time() - t0) / len(searches)
        return [(s, r, dt) for s, r in zip(searches, results)]
    raise ValueError(engine)


def run(cs=(0.5, 0.35), retrain: bool = True, verbose: bool = True,
        engine: str = "independent"):
    rows = []
    for c in cs:
        trio = _search_trio(c, engine)
        for (methods, label), (search, res, dt) in zip(AGENTS, trio):
            best = res.best_under_budget(0.05) or res.best
            acc_rt = qat_retrain(search, best.policy) if retrain else None
            rows.append({
                "table": "table1", "method": label, "c": c,
                "macs_frac": round(best.macs_frac, 4),
                "bops": best.bops,
                "latency_ratio_vs_ref": round(
                    best.latency_s / res.ref_latency_s, 4),
                "latency_vs_target": round(best.latency_ratio, 4),
                "accuracy": round(best.accuracy, 4),
                "accuracy_retrained": (round(acc_rt, 4)
                                       if acc_rt is not None else None),
                "ref_accuracy": round(res.ref_accuracy, 4),
                "episodes": len(res.history),
                "engine": engine,
                "search_s": round(dt, 1),
            })
            if verbose:
                r = rows[-1]
                print(f"[table1] {label:16s} c={c}: lat/ref="
                      f"{r['latency_ratio_vs_ref']:.3f} acc={r['accuracy']:.3f}"
                      f" (retrained {r['accuracy_retrained']}) macs="
                      f"{r['macs_frac']:.3f}", flush=True)
    return rows


def main(out="artifacts/bench_table1.json"):
    rows = run()
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    main()
