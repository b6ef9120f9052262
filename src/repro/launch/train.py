"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 200 --smoke --ckpt-dir /tmp/ckpt

On a real TPU cluster this runs one process per host (jax.distributed);
offline it runs the same code path on CPU with the smoke config. The
fault-tolerance loop: any StepTimeout / preemption -> reload latest atomic
checkpoint -> continue (data pipeline is a pure function of (seed, step)).
"""
from __future__ import annotations

import argparse

import jax

from repro.data.pipeline import DataConfig, Prefetcher, ShardedTokenDataset
from repro.distributed.fault_tolerance import StepTimeout
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import get_config
from repro.optim.optimizer import OptimizerConfig
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--data", default=None,
                    help="token-shard dir or synthetic://<vocab>")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default=None,
                    help="cosine|wsd|constant (default: per-arch)")
    ap.add_argument("--max-retries", type=int, default=3)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    # per-arch schedule default: MiniCPM trains with WSD (arXiv:2404.06395)
    schedule = args.schedule or ("wsd" if "minicpm" in args.arch
                                 else "cosine")
    opt_cfg = OptimizerConfig(lr=args.lr, schedule=schedule,
                              warmup_steps=max(10, args.steps // 20),
                              total_steps=args.steps,
                              moment_dtype="bfloat16"
                              if cfg.param_dtype == "bfloat16" else "float32")
    tcfg = TrainerConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                         ckpt_dir=args.ckpt_dir,
                         log_every=max(1, args.steps // 20))
    data_path = args.data or f"synthetic://{cfg.vocab_size}"
    ds = ShardedTokenDataset(
        data_path, DataConfig(seq_len=args.seq_len,
                              global_batch=args.global_batch,
                              shuffle_seed=0),
        host_id=jax.process_index(), num_hosts=jax.process_count())

    for attempt in range(args.max_retries):
        trainer = Trainer(cfg, opt_cfg, tcfg, seed=0)
        trainer.maybe_restore()
        start = trainer.step
        it = (ds.batch_at(s) for s in range(start, args.steps + 1))
        try:
            hist = trainer.fit(Prefetcher(iter(it), depth=2))
            for row in hist:
                print(row, flush=True)
            print(f"[train] done at step {trainer.step}; "
                  f"median step {trainer.monitor.median_step_s * 1e3:.1f}ms; "
                  f"stragglers {len(trainer.monitor.stragglers)}")
            return
        except StepTimeout as e:   # node hang -> restart from checkpoint
            print(f"[train] {e}; restarting from latest checkpoint "
                  f"(attempt {attempt + 1})", flush=True)
    raise SystemExit("exceeded retry budget")


if __name__ == "__main__":
    main()
