"""End-to-end driver: TRAIN a model with the production trainer
(checkpoint + restart safe), COMPRESS it with the Galen joint agent, QAT-
RETRAIN under the found policy, then SERVE it under sustained batched
requests.

    PYTHONPATH=src:. python examples/train_compress_serve.py \
        [--steps 200] [--episodes 30]

This is the full paper pipeline on one CPU core (~10 min). ``--steps 2``
runs the whole thing as a CI smoke: every stage scales down with the
step budget (tiny search, 4 QAT steps, short decode) but the SAME code
paths execute. On a TPU pod the same code runs with --arch
<assigned-arch> full configs (see repro/launch/train.py).
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.compress import CompressibleLM
from repro.core.ddpg import DDPGConfig
from repro.core.latency import LatencyContext
from repro.core.reward import RewardConfig
from repro.core.search import CompressionSearch, SearchConfig
from repro.data.pipeline import DataConfig, ShardedTokenDataset, bigram_lm
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import decode_loop, sustained_throughput
from repro.optim.optimizer import OptimizerConfig, adamw_init
from repro.train.train_step import make_train_step
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--episodes", type=int, default=None,
                    help="search episodes (default: 30, or 6 in smoke)")
    ap.add_argument("--target", type=float, default=0.5)
    args = ap.parse_args()
    enable_compile_cache()

    # --steps 2 is the CI smoke: every stage shrinks with the budget
    smoke = args.steps <= 10
    episodes = args.episodes if args.episodes is not None \
        else (6 if smoke else 30)
    qat_steps = 4 if smoke else 60
    serve_steps = 8 if smoke else 24
    dcfg = DDPGConfig(warmup_episodes=2 if smoke else 8,
                      updates_per_episode=2 if smoke else 16,
                      batch_size=16 if smoke else 64)

    cfg = ArchConfig(name="e2e-lm", num_layers=4, d_model=128, num_heads=8,
                     num_kv_heads=4, head_dim=16, d_ff=512, vocab_size=256)

    # ---- 1. TRAIN with the production trainer (ckpt + resume) ----
    ckpt_dir = tempfile.mkdtemp(prefix="galen_e2e_")
    opt_cfg = OptimizerConfig(lr=3e-3, warmup_steps=min(20, args.steps),
                              total_steps=args.steps, weight_decay=0.0)
    tcfg = TrainerConfig(total_steps=args.steps,
                         ckpt_every=max(1, args.steps // 2),
                         log_every=max(1, args.steps // 4),
                         ckpt_dir=ckpt_dir)
    trainer = Trainer(cfg, opt_cfg, tcfg, seed=0)
    trainer.maybe_restore()
    ds = ShardedTokenDataset(f"synthetic://{cfg.vocab_size}",
                             DataConfig(seq_len=48, global_batch=16))
    it = (ds.batch_at(s) for s in range(trainer.step, args.steps + 1))
    hist = trainer.fit(it)
    print(f"[1/4] trained {args.steps} steps; loss "
          f"{hist[-1]['loss']:.3f}; checkpoints in {ckpt_dir}")

    # ---- 2. COMPRESS: joint Galen search against the v5e oracle ----
    cm = CompressibleLM(cfg, trainer.params)
    val = ds.batch_at(10_001)
    val = {"tokens": jnp.asarray(val["tokens"])}
    ctx = LatencyContext(tokens=1, seq_ctx=512, mode="decode", batch=1)
    scfg = SearchConfig(methods="pq", episodes=episodes,
                        reward=RewardConfig(target_ratio=args.target),
                        ddpg=dcfg)
    search = CompressionSearch(cm, val, scfg, ctx)
    res = search.run(verbose=False)
    best = res.best_under_budget(0.05) or res.best
    print(f"[2/4] search: accuracy {best.accuracy:.3f} "
          f"(clean {res.ref_accuracy:.3f}) at "
          f"{best.latency_s / res.ref_latency_s:.1%} latency")

    # ---- 3. QAT RETRAIN under the found policy (paper: 30 epochs) ----
    cspec = cm.build_cspec(best.policy)
    params = trainer.params
    opt = adamw_init(params, opt_cfg)
    qat_step = jax.jit(make_train_step(cfg, opt_cfg, cspec=cspec))
    for s in range(qat_steps):
        params, opt, m = qat_step(params, opt, ds.batch_at(20_000 + s))
    cm2 = CompressibleLM(cfg, params)
    acc_rt = float(cm2.accuracy(val, cm2.build_cspec(best.policy)))
    print(f"[3/4] QAT retrain: accuracy {best.accuracy:.3f} -> {acc_rt:.3f}")

    # ---- 4. SERVE the compressed model under sustained requests ----
    cspec_final = cm2.build_cspec(best.policy)
    tokens, dt = decode_loop(cfg, params, batch=4, steps=serve_steps,
                             max_len=128, cspec=cspec_final)
    tok_s, times = sustained_throughput(
        cfg, params, batch=4, steps=serve_steps, max_len=128,
        cspec=cspec_final, requests=2 if smoke else 4)
    print(f"[4/4] served 4x{serve_steps} tokens in {dt:.2f}s; sustained "
          f"{tok_s:.1f} tok/s over batched requests "
          f"(per-request {min(times):.3f}-{max(times):.3f}s)")
    print("done.")


if __name__ == "__main__":
    main()
