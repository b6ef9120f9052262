"""The paper's core thesis, demonstrated at pod scale: the SAME model gets
DIFFERENT optimal compression policies on DIFFERENT hardware targets.

Target A: single v5e chip, batch-1 decode (edge-serving analogue).
Target B: 16-chip TP slice of a pod, batch-128 decode_32k (pod serving) —
          KV-cache traffic dominates, so the joint agent should shift
          from weight-int4 toward cache-friendly pruning.

    PYTHONPATH=src:. python examples/hardware_specific_policies.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from benchmarks.common import get_lm_testbed
from repro.core.compress import CompressibleLM
from repro.core.ddpg import DDPGConfig
from repro.core.latency import LatencyContext
from repro.core.reward import RewardConfig
from repro.core.search import CompressionSearch, SearchConfig
from repro.launch.compile_cache import enable_compile_cache


def run_target(name, ctx, episodes=30):
    cfg, params, val, _ = get_lm_testbed()
    cm = CompressibleLM(cfg, params)
    scfg = SearchConfig(methods="pq", episodes=episodes,
                        reward=RewardConfig(target_ratio=0.5),
                        ddpg=DDPGConfig(warmup_episodes=8,
                                        updates_per_episode=16,
                                        batch_size=64))
    search = CompressionSearch(cm, val, scfg, ctx)
    res = search.run(verbose=False)
    best = res.best_under_budget(0.05) or res.best
    bits = [c.w_bits for s, c in zip(search.specs, best.policy.cmps)
            if s.quantizable]
    keeps = [c.keep / s.prune_dim for s, c in
             zip(search.specs, best.policy.cmps) if s.prune_dim]
    print(f"[{name}] acc={best.accuracy:.3f} "
          f"lat={best.latency_s / res.ref_latency_s:.2%} "
          f"mean_w_bits={np.mean(bits):.1f} mean_keep={np.mean(keeps):.2f}")
    return best


def main():
    enable_compile_cache()
    edge = LatencyContext(tokens=1, seq_ctx=512, mode="decode", batch=1)
    pod = LatencyContext(tokens=128, seq_ctx=32_768, mode="decode",
                         batch=128, chips=16, tp=16)
    a = run_target("edge: 1 chip, batch-1 decode", edge)
    b = run_target("pod: 16-chip TP, batch-128 decode-32k", pod)
    same = sum(ca.mode == cb.mode and ca.keep == cb.keep
               for ca, cb in zip(a.policy.cmps, b.policy.cmps))
    print(f"\npolicies agree on {same}/{len(a.policy.cmps)} layers — "
          "hardware target changes the optimal policy (paper §Introduction)")


if __name__ == "__main__":
    main()
