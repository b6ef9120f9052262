"""Quickstart: Galen joint pruning+quantization search on a small LM.

    PYTHONPATH=src:. python examples/quickstart.py

Trains (or loads) the testbed LM, runs a short joint search against the
TPU-v5e latency oracle with a 50% latency budget, prints the best policy.
Runtime: ~3-5 min on one CPU core (first run trains the testbed).
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks.common import SERVE_CTX, get_lm_testbed
from benchmarks.policy_analysis import render_policy
from repro.core.compress import CompressibleLM
from repro.core.ddpg import DDPGConfig
from repro.core.reward import RewardConfig
from repro.core.search import CompressionSearch, SearchConfig
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    cfg, params, val, clean_acc = get_lm_testbed()
    print(f"testbed LM: {cfg.num_layers}L d={cfg.d_model} "
          f"clean accuracy {clean_acc:.3f}")
    cm = CompressibleLM(cfg, params)
    scfg = SearchConfig(
        methods="pq", episodes=30,
        reward=RewardConfig(target_ratio=0.5, beta=-3.0),
        ddpg=DDPGConfig(warmup_episodes=8, updates_per_episode=16,
                        batch_size=64, buffer_size=2000))
    print("running sensitivity analysis + 30 episodes ...")
    search = CompressionSearch(cm, val, scfg, SERVE_CTX)
    res = search.run(verbose=True)
    best = res.best_under_budget(0.05) or res.best
    print(f"\nbest policy: accuracy {best.accuracy:.3f} "
          f"(clean {res.ref_accuracy:.3f}), latency "
          f"{best.latency_s / res.ref_latency_s:.2%} of uncompressed, "
          f"MACs {best.macs_frac:.2%}")
    for line in render_policy(search.specs, best.policy):
        print("  " + line)


if __name__ == "__main__":
    main()
