"""Drive the Galen main path once on a TPU, at Qwen2-0.5B's published
widths, and check what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # the sharded fleet on four chips

One chip: random weights from ``--seed`` for the full 24-layer
configuration, the fused sensitivity analysis, two search epochs of the
epoch engine (the second past the DDPG warmup, so the update scan and
its Pallas kernels run), deployment of the best policy and of the
uncompressed model with their latencies measured on the chip, and
batched decoding with the compressed policy. The Pallas kernels of the
main path are also compared with their ``jax.numpy`` references there.

``--chips 4``: only the fleet — four search members sharded one per
device over a ``data=4`` mesh — and its single-device arm (``data=0``),
whose records must agree.

Nothing is read from ``artifacts/`` and nothing is downloaded. Without
a TPU the script exits non-zero before any work. The last line of
standard output is ``{"ok": true, "device": {...}}``; any failed check
raises first, so a run that prints it passed every phase.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Tolerances of the on-chip kernel checks against the jnp references.
# Fake quant: the kernel and XLA may round a per-channel scale apart by
# an ulp, which moves a value sitting on a floor boundary by one level;
# so every element must agree within one quantization step, and all but
# FQ_FLIP_FRAC of them within FQ_ATOL.
FQ_ATOL, FQ_FLIP_FRAC = 1e-5, 1e-3
# mlp3 against a HIGHEST-precision reference: the kernel's f32 dots may
# take the MXU's bf16 passes.
MLP_RTOL, MLP_ATOL = 1e-2, 1e-3
POLYAK_ATOL = 1e-6
# Fleet arms: records of data=4 and data=0 may differ by at most this
# (absolute, on reward / accuracy / latency); policies must be equal.
FLEET_TOL = 1e-5


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Work sizes. Per search policy the validation forward holds its
    own fake-quantized embedding table and tied head, 2 x 151936 x 896
    f32 = 1.09 GB, plus a bf16 head copy and its logits
    (val_batch * val_seq * vocab f32 = 0.16 GB at 256 tokens): about
    1.5 GB each next to the 1.98 GB of f32 weights. Four policies (or
    probes) at a time fit one 16 GB v5e with room to spare."""
    search_k: int = 4          # episodes per batch (vmapped policies)
    epoch_batches: int = 3     # batches per epoch dispatch
    epochs: int = 2
    val_batch: int = 2
    val_seq: int = 128
    sens_chunk: int = 4        # sensitivity probes evaluated at a time
    serve_batch: int = 4
    serve_steps: int = 16
    serve_requests: int = 3
    fleet_members: int = 4
    fleet_k: int = 1           # members x fleet_k policies on one device
    fleet_batches: int = 12    # one epoch straddling the 10-episode warmup


# ---------------------------------------------------------------------------
# Set-up clock and device facts
# ---------------------------------------------------------------------------

_SETUP = {"s": 0.0}


def _on_compile_event(event: str, duration: float, **_):
    if event.startswith("/jax/core/compile/"):
        _SETUP["s"] += duration


@contextlib.contextmanager
def phase(name: str):
    """Print a phase's wall seconds and the part of them JAX spent
    tracing, lowering and compiling (set-up)."""
    t0, s0 = time.perf_counter(), _SETUP["s"]
    print(f"[{name}] start", flush=True)
    yield
    print(f"[{name}] done: wall {time.perf_counter() - t0:.1f} s, "
          f"setup (trace+lower+compile) {_SETUP['s'] - s0:.1f} s",
          flush=True)


def peak_bytes() -> int:
    """``peak_bytes_in_use`` of the fullest device (0 where the backend
    keeps no statistics)."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())


def check(ok, what):
    """A check that fails the run (``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def require_tpu():
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU chip found; JAX sees {len(devs)} "
                 f"{devs[0].platform} device(s). This script measures the "
                 "chip and has no CPU fallback.")
    return devs


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------

def build_model(cfg, seed: int, sizes: Sizes):
    from repro.core.compress import CompressibleLM
    from repro.models import model as M
    params = M.init(cfg, jax.random.PRNGKey(seed))
    val = {"tokens": jax.random.randint(
        jax.random.PRNGKey(seed + 1), (sizes.val_batch, sizes.val_seq),
        0, cfg.vocab_size, jnp.int32)}
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"[model] {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{n} params (seed {seed})", flush=True)
    return CompressibleLM(cfg, params), val


def serve_ctx(sizes: Sizes):
    from repro.core.latency import LatencyContext
    return LatencyContext(tokens=1, seq_ctx=sizes.serve_steps + 1,
                          mode="decode", batch=sizes.serve_batch)


def check_sensitivity(sens):
    vals = [v for row in sens.table.values() for v in row.values()]
    check(vals and all(math.isfinite(v) and v > -1e-4 for v in vals),
          f"sensitivity KLs not finite and >= 0: {vals[:8]}")
    return vals


def check_policies(specs, policies):
    from repro.core.constraints import legalize
    from repro.core.quantization import MAX_MIX_BITS
    for pol in policies:
        for s, c in zip(specs, pol.cmps):
            check(legalize(s, copy.copy(c)) == c, (s.name, c))
            if s.prunable and s.prune_dim:
                check(1 <= c.keep <= s.prune_dim, (s.name, c))
            if c.mode == "MIX":
                check(1 <= c.w_bits <= MAX_MIX_BITS, (s.name, c))
                check(1 <= c.a_bits <= MAX_MIX_BITS, (s.name, c))


def check_records(records, n: int):
    check(len(records) == n, (len(records), n))
    for r in records:
        for k in ("reward", "accuracy", "latency_s", "latency_ratio"):
            v = getattr(r, k)
            check(math.isfinite(v), (r.episode, k, v))


# ---------------------------------------------------------------------------
# One chip: sensitivity -> search -> deploy + measure -> serve
# ---------------------------------------------------------------------------

def kernel_checks(cm, ddpg_cfg) -> dict:
    """The main path's Pallas kernels against their jnp references on
    one real weight of the model and one DDPG batch."""
    from repro.core.ddpg import _mlp_init
    from repro.core.quantization import dequantize, quantize
    from repro.kernels import ops
    w = cm.params["blocks"]["mlp"]["w_up"]["w"][0]      # (d_model, d_ff)
    out = {}
    for bits in (4, 8):
        got = ops.fused_fake_quant(w, bits)
        q, s, z = quantize(w, bits, axis=(0,))
        want = dequantize(q, s, z)
        diff = np.abs(np.asarray(got) - np.asarray(want))
        step = np.broadcast_to(1.0 / np.asarray(s), diff.shape)
        flips = float(np.mean(diff > FQ_ATOL))
        check(np.all(diff <= step * 1.001 + FQ_ATOL),
              f"fake_quant {bits}b off by more than a step: {diff.max()}")
        check(flips <= FQ_FLIP_FRAC, f"fake_quant {bits}b flips {flips}")
        out[f"fq{bits}"] = (float(diff.max()), flips)
    key = jax.random.PRNGKey(7)
    d = (ddpg_cfg.state_dim, *ddpg_cfg.hidden)
    for name, dims, final in (
            ("actor", d + (ddpg_cfg.action_dim,), "sigmoid"),
            ("critic", (d[0] + ddpg_cfg.action_dim, *d[1:], 1), "linear")):
        params = _mlp_init(key, dims)
        x = jax.random.normal(key, (ddpg_cfg.batch_size, dims[0]))
        got = np.asarray(ops.fused_mlp3(params, x, final=final))
        with jax.default_matmul_precision("highest"):
            h = x
            for i, layer in enumerate(params):
                h = h @ layer["w"] + layer["b"]
                h = jax.nn.relu(h) if i < 2 else h
            want = np.asarray(jax.nn.sigmoid(h) if final == "sigmoid"
                              else h)
        np.testing.assert_allclose(got, want, rtol=MLP_RTOL, atol=MLP_ATOL)
        out[f"mlp3_{name}"] = float(np.abs(got - want).max())
    t, p = _mlp_init(key, d + (1,)), _mlp_init(jax.random.PRNGKey(8),
                                                d + (1,))
    got = ops.fused_polyak(t, p, ddpg_cfg.tau)
    want = jax.tree.map(lambda a, b: (1 - ddpg_cfg.tau) * a
                        + ddpg_cfg.tau * b, t, p)
    err = max(float(jnp.abs(a - b).max()) for a, b in
              zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    check(err <= POLYAK_ATOL, f"polyak off by {err}")
    out["polyak"] = err
    return out


def one_chip(cfg, sizes: Sizes, seed: int) -> dict:
    from repro.core.measure import measure_policy
    from repro.core.policy import Policy
    from repro.core.search import CompressionSearch, SearchConfig
    from repro.core.sensitivity import run_sensitivity
    from repro.launch.serve import decode_loop, sustained_throughput
    from repro.train.train_step import make_prefill_step

    with phase("model"):
        cm, val = build_model(cfg, seed, sizes)

    with phase("sensitivity"):
        sens = run_sensitivity(cm, val, chunk=sizes.sens_chunk)
        kls = check_sensitivity(sens)
        print(f"[sensitivity] {len(kls)} probe KLs, max {max(kls):.4g}",
              flush=True)

    K, E = sizes.search_k, sizes.epoch_batches
    with phase("search"):
        search = CompressionSearch(
            cm, val, SearchConfig(methods="pq", oracle_mode="analytic",
                                  seed=seed),
            serve_ctx(sizes), sens=sens, batch_size=K, epoch_batches=E)
        records, scheds = [], []
        for e in range(sizes.epochs):
            first = e * K * E
            scheds.append(search._update_schedule(first, E))
            t0 = time.perf_counter()
            recs = search.run_epoch(first, E)
            records += recs
            print(f"[search] epoch {e}: episodes {first}-"
                  f"{first + K * E - 1}, update steps per batch "
                  f"{scheds[-1]}, {time.perf_counter() - t0:.2f} s, best "
                  f"reward {max(r.reward for r in recs):+.4f}", flush=True)
        check(all(n > 0 for n in scheds[-1]),
              f"last epoch is not past the warmup: {scheds}")
        check_records(records, sizes.epochs * K * E)
        check_policies(cm.specs, [r.policy for r in records])
        best = max(records, key=lambda r: r.reward)
        print(f"[search] {len(records)} episodes, best episode "
              f"{best.episode}: reward {best.reward:+.4f}, accuracy "
              f"{best.accuracy:.4f}, latency ratio "
              f"{best.latency_ratio:.4f} (analytic oracle)", flush=True)

    with phase("deploy+measure"):
        t_best = measure_policy(cm, best.policy, val)
        t_ref = measure_policy(cm, Policy.reference(cm.specs), val)
        cspec = cm.build_cspec(best.policy)
        logits = jax.jit(make_prefill_step(cfg, cspec))(cm.params,
                                                        val["tokens"])
        check(logits.shape == (*val["tokens"].shape, cfg.vocab_size),
              "logits shape")
        check(bool(jnp.all(jnp.isfinite(logits))), "non-finite logits")
        print(f"[deploy] measured forward of {sizes.val_batch}x"
              f"{sizes.val_seq} tokens: best policy {t_best * 1e3:.3f} ms, "
              f"uncompressed {t_ref * 1e3:.3f} ms, ratio "
              f"{t_best / t_ref:.4f}", flush=True)

    with phase("serve"):
        steps = sizes.serve_steps
        toks, dt = decode_loop(cfg, cm.params, sizes.serve_batch, steps,
                               steps + 1, cspec)
        toks = np.asarray(toks)
        check(toks.shape == (sizes.serve_batch, steps + 1), toks.shape)
        check(toks.min() >= 0 and toks.max() < cfg.vocab_size,
              "decoded token out of range")
        tok_s, times = sustained_throughput(
            cfg, cm.params, sizes.serve_batch, steps, steps + 1, cspec,
            requests=sizes.serve_requests)
        print(f"[serve] batch {sizes.serve_batch} x {steps} steps, "
              f"{sizes.serve_requests} requests: {tok_s:.1f} tok/s "
              f"(per request {min(times):.3f}-{max(times):.3f} s); "
              f"sample {toks[0, :8].tolist()}", flush=True)

    with phase("kernel checks"):
        errs = kernel_checks(cm, search.agent.cfg)
        print(f"[kernel checks] max |kernel - reference|: {errs}",
              flush=True)

    print(f"[memory] fullest device peak_bytes_in_use {peak_bytes()}",
          flush=True)
    return {"episodes": len(records), "best_ratio": best.latency_ratio,
            "measured_s": (t_best, t_ref), "tok_s": tok_s}


# ---------------------------------------------------------------------------
# Four chips: sharded fleet vs its single-device arm
# ---------------------------------------------------------------------------

def _fleet_records(results):
    rows, pols = [], []
    for res in results:
        for r in res.history:
            rows.append((r.reward, r.accuracy, r.latency_ratio))
            pols.append([(c.keep, c.mode, c.w_bits, c.a_bits)
                         for c in r.policy.cmps])
    return np.asarray(rows, np.float64), pols


def fleet_parity(cfg, sizes: Sizes, seed: int, data: int) -> dict:
    """Run the fleet sharded over ``data`` devices and on one device,
    from the same seeds, and compare the records."""
    from repro.core.search import (CompressionSearch, FleetSearch,
                                   SearchConfig)
    from repro.core.sensitivity import run_sensitivity
    from repro.launch.mesh import make_dev_mesh

    P, K, E = sizes.fleet_members, sizes.fleet_k, sizes.fleet_batches
    print(f"[fleet] {P} members x {K} episodes per batch x {E} batches, "
          f"one epoch; data={data} vs data=0", flush=True)
    with phase("model"):
        cm, val = build_model(cfg, seed, sizes)
    with phase("sensitivity"):
        sens = run_sensitivity(cm, val, chunk=sizes.sens_chunk)
        check_sensitivity(sens)

    def arm(mesh):
        members = [CompressionSearch(
            cm, val, SearchConfig(methods="pq", oracle_mode="analytic",
                                  seed=seed + p),
            serve_ctx(sizes), sens=sens, batch_size=K, epoch_batches=E)
            for p in range(P)]
        fleet = FleetSearch(members, mesh=mesh)
        sched = members[0]._update_schedule(0, E)
        check(any(sched), f"the fleet epoch runs no update: {sched}")
        t0 = time.perf_counter()
        results = fleet.run_fleet(K * E)
        print(f"[fleet] mesh {dict(mesh.shape) if mesh else None}: "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        if mesh is not None:
            stacked = fleet._stack_for_dispatch(
                [m.replay.data for m in members])
            leaf = jax.tree.leaves(stacked)[0]
            shards = leaf.addressable_shards
            check(len({s.device for s in shards}) == data, leaf.sharding)
            check(all(s.data.shape[0] == P // data for s in shards),
                  [s.data.shape for s in shards])
        return results

    with phase(f"fleet data={data}"):
        sharded = arm(make_dev_mesh(data=data))
    with phase("fleet data=0"):
        single = arm(None)
    rows_a, pols_a = _fleet_records(sharded)
    rows_b, pols_b = _fleet_records(single)
    check(rows_a.shape == rows_b.shape == (P * K * E, 3), rows_a.shape)
    check(np.all(np.isfinite(rows_a)), "non-finite fleet records")
    diff = float(np.abs(rows_a - rows_b).max())
    same_pols = sum(a != b for a, b in zip(pols_a, pols_b))
    print(f"[fleet] records identical: "
          f"{bool(diff == 0.0 and same_pols == 0)}; max |diff| "
          f"{diff:.3g} (tolerance {FLEET_TOL}), policies that differ "
          f"{same_pols}", flush=True)
    check(diff <= FLEET_TOL and same_pols == 0, "fleet arms disagree")
    print(f"[memory] fullest device peak_bytes_in_use {peak_bytes()}",
          flush=True)
    return {"max_diff": diff}


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = require_tpu()
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.registry import get_config
    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
    dev = devs[0]
    print(f"[device] platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devs)}; compile cache {cache}", flush=True)
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, found {len(devs)}")

    cfg = get_config("qwen2-0.5b")
    if args.chips == 4:
        fleet_parity(cfg, Sizes(), args.seed, data=4)
    else:
        one_chip(cfg, Sizes(), args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
