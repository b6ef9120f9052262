"""Fleet launcher: mesh-sharded population search with preemption-safe
epoch checkpoints (``core.search.FleetSearch``).

A fleet is P member searches — one per seed and/or hardware target —
whose stacked epoch carries are committed to a device mesh along the
member axis, so the population's single ``jit(vmap(epoch))`` dispatch
runs one member per device. Every ``--ckpt-every`` epochs the stacked
carry lands in an atomic async checkpoint; a restarted fleet restores
the newest intact step, re-shards it onto whatever mesh the surviving
devices support (``elastic_data_axis``), and resumes from the recorded
episode cursor — bit-exact when the mesh shape is unchanged.

On CPU the device count is fixed at first jax init, so multi-device
fleets need a FRESH process launched with::

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.fleet \\
      --members 4 --data 4 --episodes 32 --ckpt-dir /tmp/fleet

(the flag must precede every jax import — same recipe as
``launch/dryrun.py``). ``--data 0`` runs the same fleet without a mesh
(plain single-device PopulationSearch dispatch), which is the parity
arm the fleet tests compare against.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Optional, Sequence

import jax

from repro.core.ddpg import DDPGConfig
from repro.core.latency import LatencyContext
from repro.core.reward import RewardConfig
from repro.core.search import CompressionSearch, FleetSearch, SearchConfig
from repro.distributed.fault_tolerance import elastic_data_axis
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_dev_mesh


def fleet_data_axis(members: int, model: int = 1) -> int:
    """Data-axis extent for a fleet of ``members`` on THIS process's
    devices: the largest power-of-two the devices support, capped at the
    member count (a data axis wider than P would only shard padding)."""
    data = elastic_data_axis(1, len(jax.devices()), model)
    while data > max(1, members):
        data //= 2
    return data


def fleet_mesh(members: int, data: Optional[int] = None, model: int = 1):
    """Mesh for a fleet: ``data=None`` sizes the data axis automatically
    via ``fleet_data_axis``; ``data=0`` means no mesh (single-device
    population dispatch)."""
    if data == 0:
        return None
    if data is None:
        data = fleet_data_axis(members, model)
    return make_dev_mesh(data=data, model=model)


def tiny_fleet(members: int = 4, data: Optional[int] = None,
               methods: str = "pq", batch_size: int = 4,
               epoch_batches: int = 2, updates: int = 2, seed0: int = 0,
               warmup_episodes: int = 4, ckpt_dir: Optional[str] = None,
               ckpt_every: int = 1, mesh=None) -> FleetSearch:
    """P same-method members (one per seed) on the tiny untrained LM —
    the fleet the subprocess tests drive. Members share the model,
    validation batch, and ONE sensitivity analysis, so the fleet
    constructor pays it once and the epochs fuse into a single
    (sharded) dispatch."""
    import jax.random as jr

    from repro.configs.base import ArchConfig
    from repro.core.compress import CompressibleLM
    from repro.data.pipeline import bigram_lm
    from repro.models import model as M

    cfg = ArchConfig(name="tiny-fleet", num_layers=3, d_model=64,
                     num_heads=4, num_kv_heads=2, head_dim=16, d_ff=256,
                     vocab_size=128, scan_layers=True)
    cm = CompressibleLM(cfg, M.init(cfg, jr.PRNGKey(0)))
    batch = bigram_lm(cfg.vocab_size, 8, 32, seed=3)
    ctx = LatencyContext(tokens=1, seq_ctx=256, mode="decode", batch=1)
    engines, sens = [], None
    for p in range(members):
        scfg = SearchConfig(
            methods=methods, episodes=64,
            reward=RewardConfig(target_ratio=0.5),
            ddpg=DDPGConfig(warmup_episodes=warmup_episodes,
                            updates_per_episode=updates,
                            batch_size=16, buffer_size=256),
            seed=seed0 + p)
        m = CompressionSearch(cm, batch, scfg, ctx, sens=sens,
                              batch_size=batch_size,
                              epoch_batches=epoch_batches)
        sens = m.sens
        engines.append(m)
    if mesh is None:
        mesh = fleet_mesh(members, data)
    return FleetSearch(engines, mesh=mesh, fuse_rollouts=True,
                       ckpt_dir=ckpt_dir, ckpt_every=ckpt_every)


def _records_json(results) -> list:
    """Per-member [(episode, reward, accuracy, latency_s, sigma), ...] —
    the comparable record surface (policies compare via these)."""
    return [[(r.episode, float(r.reward), float(r.accuracy),
              float(r.latency_s), float(r.sigma)) for r in res.history]
            for res in results]


def main(argv: Optional[Sequence[str]] = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--members", type=int, default=4)
    ap.add_argument("--data", type=int, default=None,
                    help="mesh data-axis extent; 0 = no mesh "
                         "(single-device dispatch); default: largest "
                         "power of two the devices support, capped at "
                         "--members")
    ap.add_argument("--methods", default="pq")
    ap.add_argument("--episodes", type=int, default=32)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--epoch-batches", type=int, default=2)
    ap.add_argument("--updates", type=int, default=2)
    ap.add_argument("--seed0", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=1)
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest checkpoint in --ckpt-dir "
                         "before running (resumes from its cursor)")
    ap.add_argument("--stop-after-epochs", type=int, default=0,
                    help="simulate preemption: exit after N epoch "
                         "dispatches (checkpoint cadence still applies)")
    ap.add_argument("--json", action="store_true",
                    help="print a JSON result blob on the last line")
    ap.add_argument("--verbose", action="store_true")
    a = ap.parse_args(argv)
    enable_compile_cache()

    fleet = tiny_fleet(members=a.members, data=a.data, methods=a.methods,
                       batch_size=a.batch_size,
                       epoch_batches=a.epoch_batches, updates=a.updates,
                       seed0=a.seed0, ckpt_dir=a.ckpt_dir,
                       ckpt_every=a.ckpt_every)
    if a.resume:
        extra = fleet.restore_latest_checkpoint()
        if a.verbose and extra is not None:
            print(f"resumed at episode {fleet.epoch_cursor} "
                  f"(saved on mesh {extra['mesh_shape']})", flush=True)
    episodes = a.episodes
    if a.stop_after_epochs:
        per_epoch = a.batch_size * a.epoch_batches
        episodes = min(episodes, fleet.epoch_cursor
                       + a.stop_after_epochs * per_epoch)
    t0 = time.perf_counter()
    results = fleet.run_fleet(episodes, verbose=a.verbose)
    dt = time.perf_counter() - t0
    ran = sum(len(r.history) for r in results)
    out = {
        "devices": len(jax.devices()),
        "mesh": dict(fleet.mesh.shape) if fleet.mesh is not None else None,
        "members": a.members,
        "epoch_cursor": fleet.epoch_cursor,
        "epochs_run": fleet.epochs_run,
        "episodes_ran": ran,
        "eps_per_s": round(ran / dt, 3) if dt > 0 else 0.0,
        "monitor": fleet.monitor.summary(),
        "records": _records_json(results),
    }
    if a.json:
        print(json.dumps(out), flush=True)
    elif a.verbose:
        print(f"{ran} episodes in {dt:.2f}s "
              f"({out['eps_per_s']} eps/s aggregate)", flush=True)
    return out


if __name__ == "__main__":
    main()
