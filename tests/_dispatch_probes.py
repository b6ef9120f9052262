"""Dispatch-count probes: count the REAL invocations of the search's
compiled entry points by wrapping the callables themselves — not
trusting the engine's own ``dispatch_log`` — and plant canaries on the
host-stepped machinery (the numpy oracle, the host state builder, the
numpy actor), so a path that silently falls back to host steps is
caught even though it makes no jit calls.
"""
from contextlib import contextmanager

import repro.core.ddpg as ddpg_mod
import repro.core.latency as latency_mod
import repro.core.replay as replay_mod
import repro.core.sensitivity as sens_mod
import repro.core.state as state_mod


class _Patches:
    """Counting wrappers on attributes, undone in reverse order."""

    def __init__(self, counts: dict):
        self.counts = counts
        self.saved = []

    def counting(self, fn, key):
        def wrapped(*a, **kw):
            self.counts[key] += 1
            return fn(*a, **kw)
        return wrapped

    def wrap(self, obj, name, key):
        fn = getattr(obj, name)
        self.saved.append((obj, name, name in vars(obj), fn))
        setattr(obj, name, self.counting(fn, key))

    def wrap_cache(self, cache: dict, key):
        """Wrap the compiled callables of a FIFO cache of
        ``(params, fn)`` hits in place."""
        before = dict(cache)
        for k, (params, fn) in before.items():
            cache[k] = (params, self.counting(fn, key))
        self.saved.append((cache, None, None, before))

    def host_canaries(self, agent):
        self.wrap(agent, "act", "host_steps")
        self.wrap(latency_mod, "policy_latency_batch", "host_steps")
        self.wrap(state_mod, "build_state_batch", "host_steps")

    def undo(self):
        for obj, name, was_own, fn in reversed(self.saved):
            if name is None:
                obj.update(fn)
            elif was_own:
                setattr(obj, name, fn)
            else:
                delattr(obj, name)


@contextmanager
def _patched(counts: dict):
    p = _Patches(counts)
    try:
        yield p
    finally:
        p.undo()


# ------------------------------------------------------- per-batch tail

def _per_batch_counters(p, search):
    """Count the per-batch tail's compiled entry points and the host
    canaries."""
    p.wrap(search, "_rollout", "rollout")
    p.wrap(search.cmodel, "accuracy_policy_batch", "validate")
    p.wrap(replay_mod, "_device_push", "push")
    p.wrap(ddpg_mod, "_update_chunk_jit", "update")
    p.host_canaries(search.agent)


def assert_fused_dispatch_count(search, first_episode: int,
                                batch_size: int) -> dict:
    """One post-compile batch through the per-batch tail must stay
    within rollout + validation + ring write + update chunk <= 4 jit
    executions, with zero per-step host work, and the engine's
    ``dispatch_log`` must agree with the measured counts."""
    search.dispatch_log.clear()
    counts = {"rollout": 0, "validate": 0, "push": 0, "update": 0,
              "host_steps": 0}
    with _patched(counts) as p:
        _per_batch_counters(p, search)
        search.run_episode_batch(first_episode, batch_size)
        search._flush_updates()
    total = sum(counts[k] for k in ("rollout", "validate", "push",
                                    "update"))
    assert counts["host_steps"] == 0, \
        f"per-step host path ran under the per-batch tail: {counts}"
    assert total <= 4, f"per-batch tail made {total} dispatches: {counts}"
    assert len(search.dispatch_log) == total, \
        f"dispatch_log {search.dispatch_log} != measured {counts}"
    return counts


# ---------------------------------------------------------------- epochs

def assert_epoch_dispatch_count(search, first_episode: int,
                                n_batches: int) -> dict:
    """One post-compile epoch must be ONE jit execution total: the
    cached epoch executable once, the per-batch compiled entry points
    and the host path never; the ``dispatch_log`` must agree."""
    search.dispatch_log.clear()
    counts = {"epoch": 0, "rollout": 0, "validate": 0, "push": 0,
              "update": 0, "host_steps": 0}
    with _patched(counts) as p:
        p.wrap_cache(search._epoch_cache, "epoch")
        _per_batch_counters(p, search)    # canaries here: none may run
        search.run_epoch(first_episode, n_batches)
    assert counts["host_steps"] == 0, \
        f"per-step host path ran under the epoch engine: {counts}"
    per_batch = sum(counts[k] for k in ("rollout", "validate", "push",
                                        "update"))
    assert per_batch == 0, \
        f"per-batch compiled entry points ran in an epoch: {counts}"
    assert counts["epoch"] == 1, \
        f"epoch made {counts['epoch']} epoch executions " \
        f"(uncached schedule?): {counts}"
    assert search.dispatch_log == ["epoch"], search.dispatch_log
    return counts


def assert_population_epoch_dispatch_count(pop, first_episode: int,
                                           n_batches: int) -> dict:
    """One post-compile population epoch must be ONE execution of the
    shared vmapped epoch executable — never the per-member epoch
    decomposition, the per-batch entry points, or the host path — and
    every member's dispatch_log must record the one shared dispatch.
    Holds identically for the mesh-sharded ``FleetSearch``."""
    for m in pop.members:
        m.dispatch_log.clear()
    counts = {"pop_epoch": 0, "member_epoch": 0, "rollout": 0,
              "validate": 0, "push": 0, "update": 0, "host_steps": 0}
    with _patched(counts) as p:
        p.wrap_cache(pop._pop_epoch_cache, "pop_epoch")
        for m in pop.members:
            p.wrap_cache(m._epoch_cache, "member_epoch")
        _per_batch_counters(p, pop.members[0])
        pop.run_epoch(first_episode, n_batches)
    assert counts["host_steps"] == 0, \
        f"host path ran under the population epoch: {counts}"
    per_batch = sum(counts[k] for k in ("rollout", "validate", "push",
                                        "update"))
    assert per_batch == 0 and counts["member_epoch"] == 0, \
        f"population epoch fell back off the shared dispatch: {counts}"
    assert counts["pop_epoch"] == 1, \
        f"population epoch made {counts['pop_epoch']} shared executions " \
        f"(uncached schedule?): {counts}"
    for m in pop.members:
        assert m.dispatch_log == ["epoch"], m.dispatch_log
    return counts


# ----------------------------------------------------------- sensitivity

def assert_sensitivity_dispatch_count(cmodel, batch) -> dict:
    """One post-compile ``run_sensitivity`` must be ONE jit execution of
    the fused layer×probe program and ZERO per-probe evaluations of the
    sequential path."""
    sens_mod.run_sensitivity(cmodel, batch, memo=False)   # compile
    counts = {"fused": 0, "seq_probes": 0}
    with _patched(counts) as p:
        p.wrap(sens_mod, "_fused_dispatch", "fused")
        p.wrap(sens_mod, "_seq_eval", "seq_probes")
        sens_mod.run_sensitivity(cmodel, batch, memo=False)
    assert counts["seq_probes"] == 0, \
        f"per-probe sequential path ran under run_sensitivity: {counts}"
    assert counts["fused"] == 1, \
        f"run_sensitivity made {counts['fused']} fused executions: {counts}"
    return counts


# -------------------------------------------------- calibrated oracle

def synthetic_calibration():
    """Non-unity correction factors for every tiny-LM unit kind, so it
    is observable that the factors really entered the trace."""
    from repro.core.measure import CalibrationTable
    ratios = {k: {"raw": 1.1, "int8": 1.7, "int4": 2.3}
              for k in ("embed", "attn_qkv", "attn_out", "mlp_up",
                        "mlp_down", "head")}
    return CalibrationTable(ratios=ratios,
                            extra={"attn": 1.4, "overhead": 1.4},
                            meta={"synthetic": True})


def calibrated_fused_row(batch_size: int = 8, updates: int = 8) -> dict:
    """``oracle_mode="calibrated"`` must keep the per-batch tail at the
    analytic oracle's <=4-dispatch, zero-host-step bound: the correction
    factors bake into the trace as constants, they never add
    dispatches."""
    import jax
    from repro.configs.base import ArchConfig
    from repro.core.compress import CompressibleLM
    from repro.core.ddpg import DDPGConfig
    from repro.core.latency import LatencyContext
    from repro.core.reward import RewardConfig
    from repro.core.search import CompressionSearch, SearchConfig
    from repro.data.pipeline import bigram_lm
    from repro.models import model as M

    cfg = ArchConfig(name="tiny-engine", num_layers=3, d_model=64,
                     num_heads=4, num_kv_heads=2, head_dim=16, d_ff=256,
                     vocab_size=128, scan_layers=True)
    cm = CompressibleLM(cfg, M.init(cfg, jax.random.PRNGKey(0)))
    batch = bigram_lm(cfg.vocab_size, 8, 32, seed=3)
    ctx = LatencyContext(tokens=1, seq_ctx=256, mode="decode", batch=1)
    scfg = SearchConfig(
        methods="pq", episodes=64, reward=RewardConfig(target_ratio=0.5),
        ddpg=DDPGConfig(warmup_episodes=4, updates_per_episode=updates,
                        batch_size=16, buffer_size=512),
        oracle_mode="calibrated")
    s = CompressionSearch(cm, batch, scfg, ctx, batch_size=batch_size,
                          calib=synthetic_calibration())
    s.run(episodes=16)                          # warm the jit caches
    counts = assert_fused_dispatch_count(s, first_episode=16,
                                         batch_size=batch_size)
    return {"dispatches_per_batch": sum(
        counts[k] for k in ("rollout", "validate", "push", "update"))}
