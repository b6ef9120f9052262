"""The steady-state Galen search, driven through the program's epoch
engine (``FusedCompressionSearch.run_epoch``).

Set-up: weights and validation data from the seed, the sensitivity
analysis, then epochs from episode 0 until one runs the steady update
schedule (the epoch that straddles the DDPG warm-up compiles its own
program; the first steady epoch compiles the one the window drives).
The window runs steady epochs back to back; each ends with the
program's one device-to-host readback and its record building.

Correctness, once the window has closed and the program is freed:

* a seeded sample of the window's episodes (the last always in it) is
  re-scored by the plain references: the accuracy of each policy on the
  validation data (``acc_gap_median``: the median over the sample of
  the gap to the reference's accuracy; a policy with a 1-bit unit
  quantizes on a knife edge, where bfloat16 and float32 can part by 23
  of 254 positions), the oracle latency (``latency_rel_gap``), the
  reward as the reference's reward function gives it for the program's
  accuracy and the reference's latency (``reward_fn_gap``), and the
  legality of every unit (``illegal_units``);
* the agent is followed from the seed through the set-up epochs by the
  plain DDPG reference on the transitions the program wrote, and its
  parameters compared (``agent_gap``: per leaf, the gap between the
  program's and the reference's norm of the change from the initial
  parameters, over the larger of the reference's change of that leaf
  and of the median leaf).
"""
from __future__ import annotations

import gc
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import costs, harness
from chipbench import trace as trace_mod
from chipbench.reference import ddpg as ref_ddpg
from chipbench.reference import oracle as ref_oracle
from chipbench.reference.compression import effective_bits

RING_FIELDS = ("states", "actions", "rewards", "next_states", "dones")


def flops_per_episode(fam, cfg: dict, traffic: dict, state_dim: int,
                      action_dim: int, steps: int) -> float:
    ag = traffic["agent"]
    upd = ag["updates_per_episode"] * costs.ddpg_update_flops(
        state_dim, action_dim, ag["hidden"], ag["batch_size"])
    act = steps * costs.mlp3_flops(
        costs.mlp3_dims(state_dim, action_dim, ag["hidden"])["actor"], 1)
    return fam.validation_flops(cfg, traffic) + upd + act


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

def build_search(fam, cfg: dict, traffic: dict, seeds: dict, params,
                 val):
    from repro.core.ddpg import DDPGConfig
    from repro.core.latency import HardwareTarget, LatencyContext
    from repro.core.reward import RewardConfig
    from repro.core.search import FusedCompressionSearch, SearchConfig
    from repro.core.sensitivity import run_sensitivity

    model = fam.program_model(cfg, params)
    sens = run_sensitivity(model, val, chunk=traffic["sensitivity_chunk"])
    ag = dict(traffic["agent"])
    ag["hidden"] = tuple(ag["hidden"])
    scfg = SearchConfig(methods=traffic["methods"],
                        reward=RewardConfig(**traffic["reward"]),
                        ddpg=DDPGConfig(**ag), seed=seeds["agent"],
                        oracle_mode=traffic["oracle_mode"])
    hw = HardwareTarget(**traffic["oracle_hw"])
    ctx = LatencyContext(**traffic["latency_context"])
    return FusedCompressionSearch(
        model, val, scfg, ctx, hw=hw, sens=sens,
        batch_size=traffic["episodes_per_batch"],
        epoch_batches=traffic["batches_per_epoch"])


def _host_agent(st) -> dict:
    st = jax.device_get(st)
    return {g: getattr(st, g) for g in ("actor", "critic", "target_actor",
                                        "target_critic")}


def _policy_arrays(rec, order) -> tuple:
    keep, wb, ab = [], [], []
    for c in rec.policy.cmps:
        w, a = effective_bits(c.mode, c.w_bits, c.a_bits)
        keep.append(c.keep)
        wb.append(w)
        ab.append(a)
    pick = lambda x: np.asarray([x[i] for i in order], np.float64)
    return pick(keep), pick(wb), pick(ab)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def run(ctx) -> dict:
    cfg, traffic = ctx.config, ctx.traffic
    seeds = harness.sub_seeds(ctx.seed)
    fam = harness.family(cfg)
    K, E = traffic["episodes_per_batch"], traffic["batches_per_epoch"]
    steady = traffic["agent"]["updates_per_episode"] * K

    params, val = fam.make_inputs(cfg, traffic)
    search = build_search(fam, cfg, traffic, seeds, params, val)
    names = [s.name for s in search.specs]
    ref_names = fam.unit_names(cfg)
    missing = sorted(set(ref_names) - set(names))
    if missing or len(names) != len(ref_names):
        raise RuntimeError(f"program units differ from the reference's: "
                           f"{missing or names}")
    order = [names.index(n) for n in ref_names]

    # set-up epochs, up to and including the first steady one
    first, snaps = 0, []
    while True:
        sched = search._update_schedule(first, E)
        search.run_epoch(first, E)
        first += K * E
        snaps.append({"sched": sched,
                      "ring": jax.device_get(search.replay.data),
                      "agent": _host_agent(search.agent.state)})
        if all(n == steady for n in sched):
            break
        if len(snaps) > 8:
            raise RuntimeError(f"no steady update schedule: {sched}")
    jax.block_until_ready(search.agent.state)
    setup_s = harness.now() - ctx.t0

    # the window
    compiles0 = ctx.compiles.events
    win, traced = [], 0
    tw = harness.TraceWindow(ctx.workload, ctx.trace)
    n_trace = traffic["trace_epochs"] if ctx.trace else 0
    t_start = t_end = harness.now()
    epoch, span = 0, None
    while True:
        if epoch == 1 and n_trace:
            tw.start()
            span = tw.span(trace_mod.WINDOW_SPAN)
            span.__enter__()
        with tw.span("search.run_epoch"):
            win += search.run_epoch(first, E)
        first += K * E
        epoch += 1
        if span is not None and epoch == 1 + n_trace:
            span.__exit__(None, None, None)
            tw.stop()
            span, traced = None, n_trace * K * E
        t_end = harness.now()
        if t_end - t_start >= ctx.seconds and epoch > n_trace:
            break
    window_compiles = ctx.compiles.events - compiles0
    device = harness.device_facts(ctx.devices)

    state_dim = int(snaps[0]["ring"].states.shape[1])
    action_dim = int(snaps[0]["ring"].actions.shape[1])
    steps = len(search.steps)
    del search
    gc.collect()

    rng = np.random.default_rng(seeds["sample"])
    n_s = min(traffic["check_episodes"], len(win))
    pick = sorted(set(rng.choice(len(win) - 1, n_s - 1, replace=False)
                      .tolist()) | {len(win) - 1}) if len(win) > 1 else [0]
    sample = [win[i] for i in pick]
    numbers = check(fam, cfg, traffic, seeds, params, val, sample, order,
                    snaps, steps, controls=ctx.controls)
    print("sampled episodes (episode, accuracy, latency_s, reward): "
          + ", ".join(f"({r.episode}, {r.accuracy:.4f}, {r.latency_s:.4e}, "
                      f"{r.reward:.4f})" for r in sample),
          file=sys.stderr, flush=True)
    numbers["f32"]["window_compiles"] = float(window_compiles)
    failed = sum(1 for r in win if not all(
        math.isfinite(getattr(r, k)) for k in ("reward", "accuracy",
                                               "latency_s")))
    return {
        "setup_s": setup_s,
        "e2e": {"search_episodes_per_s": len(win) / (t_end - t_start)},
        "attempted": len(win), "failed": failed,
        "numbers": numbers["f32"],
        "controls": {p: numbers[p] for p in ctx.controls},
        "sample_episodes": [r.episode for r in sample],
        "device": device, "trace_dir": tw.dir if n_trace else None,
        "counters": {
            "traced_episodes": traced,
            "traced_batches": n_trace * E,
            "traced_updates": n_trace * E * steady,
            "flops_per_episode": flops_per_episode(
                fam, cfg, traffic, state_dim, action_dim, steps),
            "state_dim": state_dim, "action_dim": action_dim,
            "window_episodes": len(win)},
    }


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------

CONTROL_FORWARD = {"bfloat16": "int8", "float32": "bf16"}


def control_precisions(cfg: dict) -> dict:
    """The control: each reference one step below the precision the
    configuration states. The model forward computes in the
    configuration's ``compute_dtype``; the oracle and the agent in
    float32."""
    return {"forward": CONTROL_FORWARD[cfg["compute_dtype"]],
            "oracle": "bf16", "agent": "bf16"}


def check(fam, cfg, traffic, seeds, params, val, sample, order, snaps,
          steps, controls=()) -> dict:
    """The compared numbers: under "f32" the program against the
    reference; under "control" the reference at the control precisions
    put in the program's place, against the reference."""
    us = fam.oracle_units(cfg)
    hw, lctx = traffic["oracle_hw"], traffic["latency_context"]
    rcfg = traffic["reward"]
    ref_lat = ref_oracle.latency(us, *ref_oracle.reference_policy(us), hw,
                                 lctx)
    pols = [_policy_arrays(r, order) for r in sample]
    kinds = {"f32": {"forward": "f32", "oracle": None, "agent": "f32"}}
    if "control" in controls:
        kinds["control"] = control_precisions(cfg)

    def readings(k):
        acc_fn = fam.reference_accuracy_fn(cfg, k["forward"])
        return [(float(acc_fn(params, val, *(jnp.asarray(x, jnp.float32)
                                             for x in p))),
                 ref_oracle.latency(us, *p, hw, lctx, dtype=k["oracle"]))
                for p in pols]

    ref = readings(kinds["f32"])
    agent = follow_agent(traffic, seeds, snaps, steps,
                         {n: k["agent"] for n, k in kinds.items()})
    out = {}
    for name, k in kinds.items():
        if name == "f32":
            prog = [(r.accuracy, r.latency_s, r.reward) for r in sample]
        else:
            prog = [(a, l, ref_oracle.reward(a, l, ref_lat, rcfg))
                    for a, l in readings(k)]
        out[name] = {
            "acc_gap_median": float(np.median(
                [abs(p[0] - r[0]) for p, r in zip(prog, ref)])),
            "latency_rel_gap": max(abs(p[1] / r[1] - 1.0)
                                   for p, r in zip(prog, ref)),
            "reward_fn_gap": max(abs(p[2] - ref_oracle.reward(
                p[0], r[1], ref_lat, rcfg)) for p, r in zip(prog, ref)),
            "agent_gap": agent[name]}
        print(f"{name} per sampled episode (accuracy, reference's, "
              "lowest w/a bits, units with a_bits <= 4): " + ", ".join(
                  f"({p[0]:.4f}, {r[0]:.4f}, {int(q[1].min())}/"
                  f"{int(q[2].min())}, {int((q[2] <= 4).sum())})"
                  for p, r, q in zip(prog, ref, pols)),
              file=sys.stderr, flush=True)
    out["f32"]["illegal_units"] = float(
        sum(ref_oracle.illegal_units(us, *p) for p in pols))
    return out


def _ring_rows(snap_ring, slots) -> dict:
    return {f: np.asarray(getattr(snap_ring, f))[slots] for f in RING_FIELDS}


def follow_agent(traffic, seeds, snaps, steps, kinds: dict) -> dict:
    """Follow the agent from the seed through the set-up epochs with the
    reference (at each precision) and compare each epoch's end state
    with the program's. ``kinds`` maps a reading's name to the
    reference's precision; a reading other than "f32" puts that
    reference in the program's place and compares it with the float32
    reference."""
    ag = traffic["agent"]
    K, E = traffic["episodes_per_batch"], traffic["batches_per_epoch"]
    P = steps * K
    ring0 = snaps[0]["ring"]
    cap, sd = ring0.states.shape
    ad = ring0.actions.shape[1]
    if E * P > cap:
        raise RuntimeError("an epoch overwrites its own transitions; the "
                           "reference cannot rebuild the ring")
    fns = {}

    def chunk_fn(n, prec):
        if (n, prec) not in fns:
            fns[(n, prec)] = jax.jit(
                lambda st, ring: ref_ddpg.chunk(ag, st, ring, n, prec))
        return fns[(n, prec)]

    ends, first_grads = {p: [] for p in kinds}, None
    for name, prec in kinds.items():
        st = ref_ddpg.init(seeds["agent"], sd, ad, ag["hidden"])
        init = st
        ring = {f: np.zeros_like(np.asarray(getattr(ring0, f)))
                for f in RING_FIELDS}
        ptr = size = 0
        for snap in snaps:
            for n in snap["sched"]:
                slots = (ptr + np.arange(P)) % cap
                rows = _ring_rows(snap["ring"], slots)
                for f in RING_FIELDS:
                    ring[f][slots] = rows[f]
                ptr, size = (ptr + P) % cap, min(size + P, cap)
                st = ref_ddpg.observe(st, rows["states"])
                if n > 0:
                    dev_ring = {f: jnp.asarray(v) for f, v in ring.items()}
                    dev_ring["size"] = jnp.asarray(size, jnp.int32)
                    st, g = chunk_fn(n, prec)(st, dev_ring)
                    if first_grads is None and name == "f32":
                        first_grads = jax.device_get(g)
            ends[name].append(jax.device_get(
                {k: st[k] for k in ("actor", "critic", "target_actor",
                                    "target_critic")}))
    init = jax.device_get({k: init[k] for k in ("actor", "critic",
                                                "target_actor",
                                                "target_critic")})
    gnorm = {}
    for grp, grads in (("critic", first_grads[0]), ("actor",
                                                    first_grads[1])):
        for i, layer in enumerate(grads):
            for k, g in layer.items():
                gnorm[(grp, i, k)] = float(np.linalg.norm(g))
                gnorm[("target_" + grp, i, k)] = gnorm[(grp, i, k)]
    med_g = float(np.median(list(gnorm.values())))
    live = {k for k, v in gnorm.items() if v >= 1e-3 * med_g}
    print(f"agent leaves compared: {len(live)} of {len(gnorm)} (left out: "
          f"{sorted(set(gnorm) - live)})", file=sys.stderr, flush=True)
    out = {}
    for name in kinds:
        worst, by_epoch = 0.0, []
        for e, snap in enumerate(snaps):
            got = snap["agent"] if name == "f32" else ends[name][e]
            want = ends["f32"][e]
            d_ref, d_got = {}, {}
            for grp in init:
                for i, layer in enumerate(init[grp]):
                    for k, w0 in layer.items():
                        key = (grp, i, k)
                        if key not in live:
                            continue
                        w0 = np.asarray(w0, np.float64)
                        d_ref[key] = np.linalg.norm(
                            np.asarray(want[grp][i][k], np.float64) - w0)
                        d_got[key] = np.linalg.norm(
                            np.asarray(got[grp][i][k], np.float64) - w0)
            med = float(np.median(list(d_ref.values())))
            gaps = {key: abs(d_got[key] - d_ref[key])
                    / max(d_ref[key], med, 1e-30) for key in d_ref}
            top = max(gaps, key=gaps.get)
            by_epoch.append((gaps[top], top))
            worst = max(worst, gaps[top])
        print(f"agent_gap {name} by set-up epoch (gap, worst leaf): "
              + ", ".join(f"({g:.4f}, {k})" for g, k in by_epoch),
              file=sys.stderr, flush=True)
        out[name] = worst
    return out
