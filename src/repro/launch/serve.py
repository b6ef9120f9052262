"""Serving launcher: batched decode with a KV cache (+ optional Galen
compression policy applied at load time).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
        --batch 4 --steps 32
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.models.registry import get_config
from repro.train.train_step import make_serve_step


def decode_loop(cfg, params, batch: int, steps: int, max_len: int,
                cspec=None, prompt=None):
    step = jax.jit(make_serve_step(cfg, cspec=cspec))
    cache = M.init_cache(cfg, batch, max_len)
    toks = (prompt if prompt is not None
            else jnp.zeros((batch, 1), jnp.int32))
    out = [toks]
    t0 = time.perf_counter()
    for pos in range(steps):
        logits, cache = step(params, cache, toks, pos)
        toks = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        out.append(toks)
    jax.block_until_ready(toks)
    dt = time.perf_counter() - t0
    return jnp.concatenate(out, 1), dt


def sustained_throughput(cfg, params, batch: int, steps: int, max_len: int,
                         cspec=None, requests: int = 4):
    """Serving throughput under SUSTAINED batched requests: one jit-warm
    decode (compile + first-touch excluded), then ``requests`` fresh
    batched decode requests back to back against the same compiled step
    and a re-initialized KV cache per request — the steady-state tok/s a
    deployed (possibly compressed) model actually sustains.

    Returns ``(tok_per_s, per_request_seconds)``."""
    step = jax.jit(make_serve_step(cfg, cspec=cspec))
    prompt0 = jnp.zeros((batch, 1), jnp.int32)

    def one_request():
        cache = M.init_cache(cfg, batch, max_len)
        toks = prompt0
        for pos in range(steps):
            logits, cache = step(params, cache, toks, pos)
            toks = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        jax.block_until_ready(toks)

    one_request()                      # warm: compile + first dispatch
    times = []
    t_all = time.perf_counter()
    for _ in range(requests):
        t0 = time.perf_counter()
        one_request()
        times.append(time.perf_counter() - t0)
    dt = time.perf_counter() - t_all
    return requests * batch * steps / dt, times


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--policy", default=None,
                    help="JSON policy file from a Galen search")
    ap.add_argument("--sustained", type=int, default=0, metavar="N",
                    help="also measure steady-state tok/s over N "
                         "back-to-back batched requests")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.is_encoder:
        raise SystemExit("encoder-only arch has no decode step")
    params = M.init(cfg, jax.random.PRNGKey(0))

    cspec = None
    if args.policy:
        from repro.core.compress import CompressibleLM
        from repro.core.policy import Policy
        from repro.core.spec import LayerCMP
        with open(args.policy) as f:
            rows = json.load(f)
        cm = CompressibleLM(cfg, params)
        pol = Policy([LayerCMP(**r) for r in rows])
        cspec = cm.build_cspec(pol)

    tokens, dt = decode_loop(cfg, params, args.batch, args.steps,
                             args.max_len, cspec)
    tps = args.batch * args.steps / dt
    dev = jax.devices()[0]
    print(f"[serve] {args.arch}: {args.steps} steps x batch {args.batch} "
          f"in {dt:.2f}s -> {tps:.1f} tok/s "
          f"({dev.platform} {dev.device_kind})")
    print("[serve] sample:", tokens[0, :16].tolist())

    if args.sustained > 0:
        tok_s, times = sustained_throughput(
            cfg, params, args.batch, args.steps, args.max_len, cspec,
            requests=args.sustained)
        print(f"[serve] sustained: {args.sustained} requests -> "
              f"{tok_s:.1f} tok/s "
              f"(per-request {min(times):.3f}-{max(times):.3f}s)")


if __name__ == "__main__":
    main()
