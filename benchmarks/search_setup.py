"""Search construction shared by the paper-table benchmarks, plus the
episode-engine throughput comparisons: scalar vs batched vs fused vs
epoch-fused rollouts, and independent vs population-shared (vmapped)
agent updates.

``python -m benchmarks.search_setup`` prints episodes/sec for all of
them — plus the sequential-vs-fused sensitivity-analysis timing
(``sensitivity_comparison``, best-of-5 interleaved, with the
1-execution dispatch bound asserted) — and writes one row per engine
to ``artifacts/bench_engine.json``
(uploaded weekly by CI; ``benchmarks.regression_gate`` fails the job
when a row regresses >20% vs the committed
``artifacts/bench_baseline.json``)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time
from contextlib import contextmanager

from benchmarks.common import IMG_CTX, SERVE_CTX, get_lm_testbed, \
    get_resnet_testbed
from repro.core.compress import CompressibleLM, CompressibleResNet
from repro.core.ddpg import DDPGConfig
from repro.core.latency import LatencyContext
from repro.core.reward import RewardConfig
from repro.core.search import (BatchedCompressionSearch, CompressionSearch,
                               FusedCompressionSearch, PopulationSearch,
                               SearchConfig)
from repro.core.sensitivity import (run_sensitivity,
                                    run_sensitivity_sequential)

ENGINES = {"scalar": CompressionSearch, "batched": BatchedCompressionSearch,
           "fused": FusedCompressionSearch, "epoch": FusedCompressionSearch}

# batches fused into one dispatch by the epoch engine rows
EPOCH_BATCHES = 4

FULL = os.environ.get("GALEN_BENCH_FULL", "0") == "1"

# paper: 310 (quant) / 410 (prune, joint) episodes, 10 warm-up.
EPISODES = {"p": 410, "q": 310, "pq": 410} if FULL else \
    {"p": 60, "q": 50, "pq": 60}
WARMUP = 10
UPDATES = 48 if FULL else 24

_sens_cache = {}


def lm_search(methods: str, c: float, seed: int = 0, episodes=None,
              sens_enabled: bool = True, cls=CompressionSearch,
              action_dim: int = 0, **cls_kw) -> CompressionSearch:
    """``action_dim`` > the method's native count pads the agent's
    action space (required for mixed-method PopulationSearch members)."""
    cfg, params, val, acc = get_lm_testbed()
    # smaller eval batch: ~2x faster episodes, ±2% accuracy noise (the
    # paper also validates on a small split during search)
    val = {k: v[:32] for k, v in val.items()}
    cm = CompressibleLM(cfg, params)
    key = ("lm", sens_enabled)
    if key not in _sens_cache:
        if sens_enabled:
            _sens_cache[key] = run_sensitivity(cm, val)
        else:
            from repro.core.sensitivity import SensitivityResult
            _sens_cache[key] = SensitivityResult(
                {s.name: {} for s in cm.specs})  # constant features
    scfg = SearchConfig(
        methods=methods,
        episodes=episodes or EPISODES[methods],
        reward=RewardConfig(target_ratio=c, beta=-3.0),
        ddpg=DDPGConfig(warmup_episodes=WARMUP, updates_per_episode=UPDATES,
                        batch_size=128, buffer_size=2000,
                        action_dim=action_dim or 1),
        seed=seed)
    return cls(cm, val, scfg, SERVE_CTX, sens=_sens_cache[key], **cls_kw)


def lm_batched_search(methods: str, c: float, seed: int = 0, episodes=None,
                      sens_enabled: bool = True,
                      batch_size: int = 8) -> BatchedCompressionSearch:
    """lm_search with the batched episode engine (K episodes/rollout)."""
    return lm_search(methods, c, seed=seed, episodes=episodes,
                     sens_enabled=sens_enabled,
                     cls=BatchedCompressionSearch, batch_size=batch_size)


def lm_fused_search(methods: str, c: float, seed: int = 0, episodes=None,
                    sens_enabled: bool = True,
                    batch_size: int = 8) -> FusedCompressionSearch:
    """lm_search with the fused engine (whole rollout = one dispatch)."""
    return lm_search(methods, c, seed=seed, episodes=episodes,
                     sens_enabled=sens_enabled,
                     cls=FusedCompressionSearch, batch_size=batch_size)


def resnet_search(methods: str, c: float, seed: int = 0,
                  episodes=None) -> CompressionSearch:
    rcfg, params, val, acc = get_resnet_testbed()
    cm = CompressibleResNet(rcfg, params)
    if "resnet" not in _sens_cache:
        _sens_cache["resnet"] = run_sensitivity(cm, val)
    scfg = SearchConfig(
        methods=methods,
        episodes=episodes or EPISODES[methods],
        reward=RewardConfig(target_ratio=c, beta=-3.0),
        ddpg=DDPGConfig(warmup_episodes=WARMUP, updates_per_episode=UPDATES,
                        batch_size=128, buffer_size=2000),
        seed=seed)
    return CompressionSearch(cm, val, scfg, IMG_CTX,
                             sens=_sens_cache["resnet"])


# ===========================================================================
# Episode-engine throughput: scalar loop vs batched rollout
# ===========================================================================

_tiny_testbed_cache = {}


def _tiny_testbed():
    """Tiny untrained LM + shared sensitivity — engine overhead
    dominates its episodes, which is what these comparisons isolate."""
    if "lm" not in _tiny_testbed_cache:
        import jax
        from repro.configs.base import ArchConfig
        from repro.data.pipeline import bigram_lm
        from repro.models import model as M

        cfg = ArchConfig(name="tiny-engine", num_layers=3, d_model=64,
                         num_heads=4, num_kv_heads=2, head_dim=16, d_ff=256,
                         vocab_size=128, scan_layers=True)
        params = M.init(cfg, jax.random.PRNGKey(0))
        batch = bigram_lm(cfg.vocab_size, 8, 32, seed=3)
        _tiny_testbed_cache["lm"] = (CompressibleLM(cfg, params), batch)
    return _tiny_testbed_cache["lm"]


def _tiny_engine(engine, batch_size: int, updates: int,
                 methods: str = "pq", action_dim: int = 0, seed: int = 0,
                 calib=None):
    """``engine``: "scalar" | "batched" | "fused" | "epoch" (bools kept
    for the original scalar/batched call sites). ``calib`` switches the
    engine to ``oracle_mode="calibrated"`` with that table."""
    if isinstance(engine, bool):
        engine = "batched" if engine else "scalar"
    cm, batch = _tiny_testbed()
    ctx = LatencyContext(tokens=1, seq_ctx=256, mode="decode", batch=1)
    scfg = SearchConfig(
        methods=methods, episodes=64, reward=RewardConfig(target_ratio=0.5),
        ddpg=DDPGConfig(warmup_episodes=4, updates_per_episode=updates,
                        batch_size=16, buffer_size=512,
                        action_dim=action_dim or 1),
        seed=seed,
        oracle_mode="calibrated" if calib is not None else "analytic")
    cls = ENGINES[engine]
    kw = {} if calib is None else {"calib": calib}
    if engine == "scalar":
        return cls(cm, batch, scfg, ctx, **kw)
    if engine == "epoch":
        return cls(cm, batch, scfg, ctx, batch_size=batch_size,
                   epoch_batches=EPOCH_BATCHES, **kw)
    return cls(cm, batch, scfg, ctx, batch_size=batch_size, **kw)


def synthetic_calibration():
    """Non-unity correction factors for every tiny-LM unit kind — a
    stand-in for the committed artifact that makes it observable (in
    unit tests and the dispatch probe) that the factors really entered
    the trace."""
    from repro.core.measure import CalibrationTable
    ratios = {k: {"raw": 1.1, "int8": 1.7, "int4": 2.3}
              for k in ("embed", "attn_qkv", "attn_out", "mlp_up",
                        "mlp_down", "head")}
    return CalibrationTable(ratios=ratios,
                            extra={"attn": 1.4, "overhead": 1.4},
                            meta={"synthetic": True})


def calibrated_fused_row(batch_size: int = 8, updates: int = 8) -> dict:
    """ISSUE 6 acceptance: ``oracle_mode="calibrated"`` must keep the
    fused engine at the same <=4-dispatch, zero-host-step bound as the
    analytic oracle — the correction factors bake into the trace as
    constants, they never add dispatches."""
    s = _tiny_engine("fused", batch_size, updates,
                     calib=synthetic_calibration())
    s.run(episodes=16)                          # warm the jit caches
    counts = assert_fused_dispatch_count(s, first_episode=16,
                                         batch_size=batch_size)
    return {"table": "engine", "engine": "fused_calibrated",
            "batch_size": batch_size, "updates_per_episode": updates,
            "dispatches_per_batch": sum(
                counts[k] for k in ("rollout", "validate", "push",
                                    "update"))}


def episodes_per_sec(search, episodes: int = 32,
                     warmup_episodes: int = 16, repeats: int = 3) -> float:
    # warm the jit caches over TWO chunks: the first chunk straddles the
    # agent's warmup boundary, so its fused update chunk is shorter than
    # the steady-state one — both scan lengths must compile here, not in
    # the timed region
    search.run(episodes=warmup_episodes)
    # best-of-N: shared CI/dev boxes show ±20% run-to-run contention
    # noise, and the minimum is the stable estimate of engine cost
    import jax
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        search.run(episodes=episodes)
        # the final fused update chunk is dispatched asynchronously —
        # fence it so the timed region contains all of its work
        jax.block_until_ready(search.agent.state)
        best = min(best, time.perf_counter() - t0)
    return episodes / best


@contextmanager
def fused_dispatch_probe(search):
    """Compile-counter hook: counts REAL invocations of the fused
    path's compiled entry points (rollout jit, fused validation jit,
    replay ring-write jit, update-chunk jit) by wrapping the callables
    themselves — not trusting the engine's own ``dispatch_log`` — and
    plants canaries on the per-step host path (``act_batch``, the numpy
    batch oracle) so a regression that silently falls back to L host
    steps per batch is caught even though it makes no jit calls."""
    import repro.core.ddpg as ddpg_mod
    import repro.core.replay as replay_mod
    import repro.core.search as search_mod
    counts = {"rollout": 0, "validate": 0, "push": 0, "update": 0,
              "host_steps": 0}
    saved = []

    def wrap(obj, name, key):
        fn = getattr(obj, name)
        saved.append((obj, name, name in vars(obj), fn))

        def counting(*a, **kw):
            counts[key] += 1
            return fn(*a, **kw)

        setattr(obj, name, counting)

    wrap(search, "_rollout", "rollout")
    wrap(search.cmodel, "accuracy_policy_batch", "validate")
    wrap(replay_mod, "_device_push", "push")
    wrap(ddpg_mod, "_update_chunk_jit", "update")
    # canaries — the numpy engines' per-unit-step host machinery
    wrap(search.agent, "act_batch", "host_steps")
    wrap(search_mod, "policy_latency_batch", "host_steps")
    try:
        yield counts
    finally:
        for obj, name, was_own, fn in reversed(saved):
            if was_own:
                setattr(obj, name, fn)
            else:
                delattr(obj, name)


def assert_fused_dispatch_count(search, first_episode: int,
                                batch_size: int) -> dict:
    """One post-compile episode batch on the fused engine must stay
    within the ISSUE 3 bound: rollout + validation + ring write +
    update chunk <= 4 jit executions, zero per-step host work. Also
    checks the engine's ``dispatch_log`` agrees with the measured
    counts. Runs in the weekly job; a regression fails it."""
    search.dispatch_log.clear()
    with fused_dispatch_probe(search) as counts:
        search.run_episode_batch(first_episode, batch_size)
        search._flush_updates()
    total = sum(counts[k] for k in ("rollout", "validate", "push",
                                    "update"))
    assert counts["host_steps"] == 0, \
        f"per-step host path ran under the fused engine: {counts}"
    assert total <= 4, f"fused engine made {total} dispatches: {counts}"
    assert len(search.dispatch_log) == total, \
        f"dispatch_log {search.dispatch_log} != measured {counts}"
    return counts


@contextmanager
def epoch_dispatch_probe(search):
    """Epoch-mode compile-counter hook: counts REAL invocations of the
    cached epoch executables (by wrapping the compiled callables in the
    engine's FIFO cache), and plants canaries on EVERY per-batch entry
    point — the fused rollout jit, the standalone validation jit, the
    ring-write jit, the update-chunk jit, and the numpy engines' host
    machinery. An epoch must touch none of them: the whole E-batch
    epoch is the one compiled program."""
    import repro.core.ddpg as ddpg_mod
    import repro.core.replay as replay_mod
    import repro.core.search as search_mod
    counts = {"epoch": 0, "rollout": 0, "validate": 0, "push": 0,
              "update": 0, "host_steps": 0}
    saved = []

    def wrap(obj, name, key):
        fn = getattr(obj, name)
        saved.append((obj, name, name in vars(obj), fn))

        def counting(*a, **kw):
            counts[key] += 1
            return fn(*a, **kw)

        setattr(obj, name, counting)

    # the compiled epoch executables live in the engine's FIFO cache as
    # (params, fn) hits — wrap each fn in place
    cache_saved = dict(search._epoch_cache)

    def wrap_cache_entry(k, params, fn):
        def counting(*a, **kw):
            counts["epoch"] += 1
            return fn(*a, **kw)

        search._epoch_cache[k] = (params, counting)

    for k, (params, fn) in cache_saved.items():
        wrap_cache_entry(k, params, fn)
    # canaries: the per-batch fused path and the numpy host path
    wrap(search, "_rollout", "rollout")
    wrap(search.cmodel, "accuracy_policy_batch", "validate")
    wrap(replay_mod, "_device_push", "push")
    wrap(ddpg_mod, "_update_chunk_jit", "update")
    wrap(search.agent, "act_batch", "host_steps")
    wrap(search_mod, "policy_latency_batch", "host_steps")
    try:
        yield counts
    finally:
        for obj, name, was_own, fn in reversed(saved):
            if was_own:
                setattr(obj, name, fn)
            else:
                delattr(obj, name)
        search._epoch_cache.update(cache_saved)


def assert_epoch_dispatch_count(search, first_episode: int,
                                n_batches: int) -> dict:
    """One post-compile epoch on the epoch-fused engine must be ONE jit
    execution total (the ISSUE 4 acceptance bound): the epoch
    executable once, the per-batch compiled entry points and the host
    path never. Also checks the engine's ``dispatch_log`` agrees. Runs
    in the weekly job; a regression fails it."""
    search.dispatch_log.clear()
    with epoch_dispatch_probe(search) as counts:
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


@contextmanager
def population_epoch_dispatch_probe(pop):
    """Shared-epoch compile-counter hook for ``PopulationSearch`` /
    ``FleetSearch``: counts REAL invocations of the population's compiled
    epoch executables (wrapping the callables in ``_pop_epoch_cache``)
    and plants canaries on every fallback — the members' own epoch
    caches (the per-member decomposition), the per-batch fused entry
    points, and the numpy host path. One population epoch must execute
    the shared program exactly once and touch nothing else, mesh-sharded
    or not."""
    import repro.core.ddpg as ddpg_mod
    import repro.core.replay as replay_mod
    import repro.core.search as search_mod
    counts = {"pop_epoch": 0, "member_epoch": 0, "rollout": 0,
              "validate": 0, "push": 0, "update": 0, "host_steps": 0}
    saved = []

    def wrap(obj, name, key):
        fn = getattr(obj, name)
        saved.append((obj, name, name in vars(obj), fn))

        def counting(*a, **kw):
            counts[key] += 1
            return fn(*a, **kw)

        setattr(obj, name, counting)

    def wrap_cache(cache, key):
        before = dict(cache)
        for k, (params, fn) in before.items():
            def make(fn):
                def counting(*a, **kw):
                    counts[key] += 1
                    return fn(*a, **kw)
                return counting
            cache[k] = (params, make(fn))
        return before

    pop_saved = wrap_cache(pop._pop_epoch_cache, "pop_epoch")
    member_saved = [(m, wrap_cache(m._epoch_cache, "member_epoch"))
                    for m in pop.members]
    m0 = pop.members[0]
    wrap(m0, "_rollout", "rollout")
    wrap(m0.cmodel, "accuracy_policy_batch", "validate")
    wrap(replay_mod, "_device_push", "push")
    wrap(ddpg_mod, "_update_chunk_jit", "update")
    wrap(m0.agent, "act_batch", "host_steps")
    wrap(search_mod, "policy_latency_batch", "host_steps")
    try:
        yield counts
    finally:
        for obj, name, was_own, fn in reversed(saved):
            if was_own:
                setattr(obj, name, fn)
            else:
                delattr(obj, name)
        pop._pop_epoch_cache.update(pop_saved)
        for m, cs in member_saved:
            m._epoch_cache.update(cs)


def assert_population_epoch_dispatch_count(pop, first_episode: int,
                                           n_batches: int) -> dict:
    """One post-compile population epoch must be ONE execution of the
    shared vmapped epoch executable — never the per-member epoch
    decomposition, the per-batch entry points, or the host path — and
    every member's dispatch_log must record the one shared dispatch.
    Holds identically for the mesh-sharded ``FleetSearch`` (the sharded
    program is the same cached executable compiled for sharded
    operands). Runs in the fleet tests and the weekly job."""
    for m in pop.members:
        m.dispatch_log.clear()
    with population_epoch_dispatch_probe(pop) as counts:
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


@contextmanager
def sensitivity_dispatch_probe():
    """Compile-counter hook for the sensitivity subsystem: counts REAL
    executions of the fused layer×probe program (by wrapping the module
    indirection the compiled callable is dispatched through) and plants
    a canary on the sequential path's per-probe evaluations — a fused
    analysis that silently falls back to L×probe dispatches is caught
    even though each one is a legitimate jit call."""
    import repro.core.sensitivity as sens_mod
    counts = {"fused": 0, "seq_probes": 0}
    saved_f, saved_s = sens_mod._fused_dispatch, sens_mod._seq_eval

    def fused(fn, *a):
        counts["fused"] += 1
        return saved_f(fn, *a)

    def seq(fn, cs):
        counts["seq_probes"] += 1
        return saved_s(fn, cs)

    sens_mod._fused_dispatch, sens_mod._seq_eval = fused, seq
    try:
        yield counts
    finally:
        sens_mod._fused_dispatch, sens_mod._seq_eval = saved_f, saved_s


def assert_sensitivity_dispatch_count(cmodel, batch) -> dict:
    """One post-compile ``run_sensitivity`` must be ONE jit execution of
    the fused program and ZERO per-probe evaluations (the ISSUE 5
    acceptance bound, the sensitivity analogue of
    ``assert_epoch_dispatch_count``). Runs in the weekly job; a
    regression fails it."""
    run_sensitivity(cmodel, batch, memo=False)      # compile outside
    with sensitivity_dispatch_probe() as counts:
        run_sensitivity(cmodel, batch, memo=False)
    assert counts["seq_probes"] == 0, \
        f"per-probe sequential path ran under run_sensitivity: {counts}"
    assert counts["fused"] == 1, \
        f"run_sensitivity made {counts['fused']} fused executions: {counts}"
    return counts


def sensitivity_comparison(repeats: int = 5, verbose: bool = True) -> list:
    """Sequential vs fused ``run_sensitivity`` wall time on the tiny LM
    (the analysis every engine constructor pays), best-of-N interleaved
    round-robin like ``engine_comparison`` so box drift hits both arms
    equally. The throughput metric (analyses/sec) keeps the regression
    gate's lower-is-worse rule; the fused row also re-asserts the
    1-execution dispatch bound."""
    cm, batch = _tiny_testbed()
    arms = {"sequential": lambda: run_sensitivity_sequential(cm, batch),
            "fused": lambda: run_sensitivity(cm, batch, memo=False)}
    for fn in arms.values():
        fn()                                        # warm the jit caches
    best = {name: 0.0 for name in arms}
    for _ in range(repeats):
        for name, fn in arms.items():
            t0 = time.perf_counter()
            fn()                                    # result is host data
            best[name] = max(best[name],
                             1.0 / (time.perf_counter() - t0))
    assert_sensitivity_dispatch_count(cm, batch)
    rows = [{"table": "sensitivity", "engine": "sequential",
             "runs_per_s": round(best["sequential"], 3)},
            {"table": "sensitivity", "engine": "fused",
             "runs_per_s": round(best["fused"], 3),
             "dispatches_per_run": 1,
             "speedup_vs_sequential": round(
                 best["fused"] / best["sequential"], 2)}]
    if verbose:
        print(f"[sensitivity] sequential {best['sequential']:.2f} runs/s, "
              f"fused {best['fused']:.2f} runs/s -> "
              f"{best['fused'] / best['sequential']:.2f}x", flush=True)
    return rows


def engine_comparison(batch_size: int = 8, episodes: int = 32,
                      updates: int = 0, verbose: bool = True) -> list:
    """Episodes/sec on the tiny LM, one row per engine.

    ``updates=0`` isolates rollout+validation throughput — where the
    one-dispatch rollout pays off most; with updates enabled every
    engine dispatches each episode batch's updates as one fused
    ``update_chunk`` scan (PR 2), so the rollout engines amortize
    rollout AND learning dispatch. The epoch engine additionally fuses
    ``EPOCH_BATCHES`` whole batches (rollout+validate+push+update) into
    one jit execution with a single host readback.
    """
    import jax
    names = ("scalar", "batched", "fused", "epoch")
    searches = {}
    for name in names:
        s = _tiny_engine(name, batch_size, updates)
        # warm the jit caches over two chunks straddling the agent's
        # warmup boundary; the epoch engine warms a full run so the
        # timed chunks hit its compiled (warmup-straddling) schedule
        s.run(episodes=episodes if name == "epoch" else 16)
        jax.block_until_ready(s.agent.state)
        searches[name] = s
    # interleave the best-of-N repeats round-robin across engines so
    # box-level drift (thermal, contention) hits every engine equally
    # instead of penalizing whichever is measured last; N=5 because the
    # engines differ by less than this box's run-to-run spread
    eps = {n: 0.0 for n in names}
    for _ in range(5):
        for name, s in searches.items():
            t0 = time.perf_counter()
            s.run(episodes=episodes)
            # final dispatches are asynchronous — fence them into the
            # timed region
            jax.block_until_ready(s.agent.state)
            eps[name] = max(eps[name],
                            episodes / (time.perf_counter() - t0))
    rows = []
    for name in names:
        search = searches[name]
        row = {"table": "engine", "engine": name,
               "batch_size": batch_size, "episodes": episodes,
               "updates_per_episode": updates,
               "eps_per_s": round(eps[name], 2)}
        if name == "batched":
            row["speedup_vs_scalar"] = round(eps[name] / eps["scalar"],
                                             2)
        elif name == "fused":
            counts = assert_fused_dispatch_count(
                search, first_episode=2 * episodes,
                batch_size=batch_size)
            row["dispatches_per_batch"] = sum(
                counts[k] for k in ("rollout", "validate", "push",
                                    "update"))
            row["speedup_vs_batched"] = round(eps[name] / eps["batched"],
                                              2)
        elif name == "epoch":
            # first_episode=0 reuses the schedule the timed runs
            # compiled (run() restarts episode numbering each call)
            assert_epoch_dispatch_count(search, first_episode=0,
                                        n_batches=EPOCH_BATCHES)
            row["epoch_batches"] = EPOCH_BATCHES
            row["dispatches_per_epoch"] = 1
            row["speedup_vs_fused"] = round(eps[name] / eps["fused"], 2)
        rows.append(row)
    if verbose:
        print(f"[engine] K={batch_size} updates={updates}: "
              + ", ".join(f"{n} {eps[n]:.1f} eps/s"
                          for n in ("scalar", "batched", "fused",
                                    "epoch"))
              + f" -> epoch/fused {eps['epoch'] / eps['fused']:.2f}x",
              flush=True)
    return rows


def population_comparison(batch_size: int = 8, episodes: int = 32,
                          updates: int = 8, verbose: bool = True) -> dict:
    """Aggregate episodes/sec for the paper's p/q/pq agent trio:
    three independent batched searches vs one PopulationSearch whose
    members share each update dispatch via ``jit(vmap(update_chunk))``.

    Action dims are padded to the joint agent's 3 in both arms so the
    comparison isolates dispatch sharing, not network sizes.
    """
    methods = ("p", "q", "pq")
    warm, total = 16, episodes * len(methods)   # 2 chunks: see above

    def fresh(seed0):
        return [_tiny_engine(True, batch_size, updates, methods=m,
                             action_dim=3, seed=seed0 + i)
                for i, m in enumerate(methods)]

    import jax

    def fence(ms):      # async update chunks must land inside the timer
        for m in ms:
            jax.block_until_ready(m.agent.state)

    # --- independent: each member flushes its own fused update chunks
    members = fresh(0)
    for m in members:
        m.run(episodes=warm)             # warm the jit caches
    indep = 0.0
    for _ in range(3):                   # best-of-N (see episodes_per_sec)
        t0 = time.perf_counter()
        for m in members:
            m.run(episodes=episodes)
        fence(members)
        indep = max(indep, total / (time.perf_counter() - t0))

    # --- population: one vmapped update dispatch for all members
    pop = PopulationSearch(fresh(100))
    pop.run(episodes=warm)
    shared = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        pop.run(episodes=episodes)
        fence(pop.members)
        shared = max(shared, total / (time.perf_counter() - t0))

    out = {"table": "population", "members": list(methods),
           "batch_size": batch_size, "episodes_per_member": episodes,
           "updates_per_episode": updates,
           "independent_eps_per_s": round(indep, 2),
           "population_eps_per_s": round(shared, 2),
           "speedup": round(shared / indep, 2)}
    if verbose:
        print(f"[population] P={len(methods)} K={batch_size} "
              f"updates={updates}: independent {indep:.1f} eps/s, "
              f"shared-dispatch {shared:.1f} eps/s "
              f"-> {shared / indep:.2f}x", flush=True)
    return out


# ===========================================================================
# Update floor: vmap reference vs megabatched population chunks (ISSUE 7)
# ===========================================================================

def _paper_population(P: int, seed: int = 0):
    """P paper-sized agents ((400, 300) hidden, batch 128) with filled
    device replays — the exact update workload PopulationSearch
    dispatches."""
    import jax
    import numpy as np
    from repro.core.ddpg import agent_init, tree_stack
    from repro.core.replay import DeviceReplay
    cfg = DDPGConfig(state_dim=10, action_dim=3, batch_size=128,
                     buffer_size=2000)
    rng = np.random.default_rng(seed)
    states, replays = [], []
    for p in range(P):
        states.append(agent_init(cfg, jax.random.PRNGKey(seed + p)))
        rep = DeviceReplay(cfg.buffer_size, cfg.state_dim, cfg.action_dim)
        n = 600
        rep.push_batch(
            rng.standard_normal((n, cfg.state_dim)).astype(np.float32),
            rng.uniform(size=(n, cfg.action_dim)).astype(np.float32),
            rng.standard_normal(n).astype(np.float32),
            rng.standard_normal((n, cfg.state_dim)).astype(np.float32),
            rng.integers(0, 2, n).astype(np.float32))
        replays.append(rep.data)
    return cfg, tree_stack(states), tree_stack(replays)


def _print_update_gemm_shapes(cfg, P: int):
    """The GEMM shapes each path dispatches per scan step — so floor
    regressions are diagnosable from the benchmark log alone."""
    B = cfg.batch_size
    h1, h2 = cfg.hidden
    S, A = cfg.state_dim, cfg.action_dim
    critic = [(S + A, h1), (h1, h2), (h2, 1)]
    actor = [(S, h1), (h1, h2), (h2, A)]
    print(f"  [shapes] P={P} B={B}: per-layer GEMMs (fwd) "
          + " ".join(f"({P},{B},{i})x({P},{i},{o})"
                     for i, o in critic + actor)
          + f"; bwd dW einsum pbi,pbo->pio, dx einsum pbo,pio->pbi; "
          f"both paths batch over P (vmap via batched dot_general, "
          f"megabatch explicitly)", flush=True)


@contextmanager
def megabatch_dispatch_probe():
    """Compile-counter hook for the population update path: counts REAL
    invocations of the megabatched compiled entries (plain + donating)
    and plants canaries on the vmap population jit and the per-member
    update-chunk jit — a silent fallback to either is caught."""
    import repro.core.ddpg as ddpg_mod
    counts = {"mega": 0, "vmap": 0, "member": 0}
    names = {"_population_update_chunk_mega_jit": "mega",
             "_population_update_chunk_mega_donate_jit": "mega",
             "_population_update_chunk_jit": "vmap",
             "_update_chunk_jit": "member"}
    saved = {}

    def wrap(name, key):
        fn = getattr(ddpg_mod, name)
        saved[name] = fn

        def counting(*a, **kw):
            counts[key] += 1
            return fn(*a, **kw)

        setattr(ddpg_mod, name, counting)

    for name, key in names.items():
        wrap(name, key)
    try:
        yield counts
    finally:
        for name, fn in saved.items():
            setattr(ddpg_mod, name, fn)


def assert_megabatch_dispatch_count(cfg, states, replays, n: int) -> dict:
    """One routed population chunk must be exactly ONE execution of the
    megabatched compiled entry — never the vmap reference or P
    per-member chunks. Runs in the weekly job; a regression fails it."""
    from repro.core.ddpg import population_update_chunk
    population_update_chunk(cfg, states, replays, n)    # compile outside
    with megabatch_dispatch_probe() as counts:
        population_update_chunk(cfg, states, replays, n)
    assert counts["mega"] == 1, \
        f"population chunk made {counts['mega']} megabatch executions: " \
        f"{counts}"
    assert counts["vmap"] == 0 and counts["member"] == 0, \
        f"population chunk fell back off the megabatched path: {counts}"
    return counts


def update_floor_comparison(pops=(1, 4, 16), updates: int = 8,
                            repeats: int = 5, verbose: bool = True) -> list:
    """ms/update of the DDPG population chunk, vmap reference vs the
    megabatched path, at P member counts. Best-of-N interleaved
    round-robin (box drift hits both arms equally); the megabatched arm
    runs the production donating entry, so each rep feeds it a fresh
    copy of the stacked states (copies made OUTSIDE the timed region).

    ``ms_per_update`` is wall ms per scan step (all P members advance
    one update); ``ms_per_member_update`` divides by P."""
    import jax
    import jax.numpy as jnp
    from repro.core.ddpg import (population_update_chunk_megabatched,
                                 population_update_chunk_vmap)
    rows = []
    for P in pops:
        cfg, states, replays = _paper_population(P)
        if verbose:
            _print_update_gemm_shapes(cfg, P)
        copy = lambda: jax.tree.map(jnp.copy, states)
        arms = {
            "vmap": lambda s: population_update_chunk_vmap(
                cfg, s, replays, updates),
            "megabatch": lambda s: population_update_chunk_megabatched(
                cfg, s, replays, updates, donate=True),
        }
        for fn in arms.values():
            jax.block_until_ready(fn(copy())[0])        # warm the jits
        best = {name: float("inf") for name in arms}
        for _ in range(repeats):
            for name, fn in arms.items():
                s = copy()
                jax.block_until_ready(s)
                t0 = time.perf_counter()
                out, _ = fn(s)
                jax.block_until_ready(out)
                best[name] = min(best[name], time.perf_counter() - t0)
        counts = assert_megabatch_dispatch_count(
            cfg, copy(), replays, updates)
        for name in arms:
            ms = best[name] * 1000.0 / updates
            row = {"table": "update_floor", "engine": name, "members": P,
                   "batch_size": cfg.batch_size,
                   "updates_per_episode": updates,
                   "ms_per_update": round(ms, 3),
                   "ms_per_member_update": round(ms / P, 3)}
            if name == "megabatch":
                row["dispatches_per_chunk"] = counts["mega"]
                row["speedup_vs_vmap"] = round(
                    best["vmap"] / best["megabatch"], 3)
            rows.append(row)
        if verbose:
            print(f"[update_floor] P={P} n={updates}: "
                  f"vmap {best['vmap'] * 1000 / updates:.2f} ms/update, "
                  f"megabatch {best['megabatch'] * 1000 / updates:.2f} "
                  f"ms/update -> "
                  f"{best['vmap'] / best['megabatch']:.2f}x", flush=True)
    return rows


# ===========================================================================
# Fleet scaling: mesh-sharded population epochs, 1 vs 4 devices (ISSUE 8)
# ===========================================================================

FLEET_SCALING_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import json
    import time
    import jax
    from benchmarks.search_setup import \\
        assert_population_epoch_dispatch_count
    from repro.launch.fleet import tiny_fleet

    P, E, EPISODES, REPS = 4, 2, 16, 5
    arms = {
        "fleet_1dev": tiny_fleet(members=P, data=0, updates=2, seed0=0),
        "fleet_4dev": tiny_fleet(members=P, data=4, updates=2, seed0=0),
    }
    # warm: the first chunk straddles the agent's warmup boundary, so
    # both the warmup-straddling and the steady epoch schedules compile
    # here, outside the timed region
    for f in arms.values():
        f.run_fleet(f.epoch_cursor + EPISODES)
    best = {n: 0.0 for n in arms}
    for _ in range(REPS):
        for n, f in arms.items():
            t0 = time.perf_counter()
            f.run_fleet(f.epoch_cursor + EPISODES)
            best[n] = max(best[n],
                          P * EPISODES / (time.perf_counter() - t0))
    probe = assert_population_epoch_dispatch_count(
        arms["fleet_4dev"], arms["fleet_4dev"].epoch_cursor, E)
    print(json.dumps({"eps": best, "devices": len(jax.devices()),
                      "platform": jax.devices()[0].platform,
                      "pop_epoch": probe["pop_epoch"]}))
""")


def fleet_scaling_rows(verbose: bool = True) -> list:
    """Aggregate eps/s of a P=4 ``FleetSearch`` (updates>0) with the
    same workload pinned to one device vs sharded over a 4-device mesh,
    best-of-5 interleaved round-robin. Runs in a FRESH subprocess — the
    CPU device count locks at first jax init, so the forced-host-device
    recipe cannot run in the benchmark process itself.

    Honest-measurement note (the PR 7 precedent): on this 1-core CI box
    every forced host device shares the same core, so the 4-device arm
    measures ~1x the 1-device arm — the sharded program's win needs
    genuinely parallel devices. The rows pin the sharded dispatch path
    (the probe asserts the 1-execution bound) and its eps/s against
    regression; the >=2x multiple lives on real multi-device backends.

    The child forces the CPU backend, so on a TPU host its "devices"
    would be host threads: this refuses to run there (the fleet on real
    chips runs through ``chip_smoke.py --chips 4``), and every row names
    the platform it ran on.
    """
    import jax
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "fleet_scaling_rows times forced CPU host devices in a child "
            "process; on a TPU host they would be reported as devices. "
            "Run the fleet on the chips with `python chip_smoke.py "
            "--chips 4` instead.")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + root
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", FLEET_SCALING_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=1800, cwd=root)
    if res.returncode != 0:
        raise RuntimeError(
            f"fleet_scaling subprocess failed:\n{res.stderr[-3000:]}")
    out = json.loads(res.stdout.strip().splitlines()[-1])
    rows = []
    for name, devices in (("fleet_1dev", 1), ("fleet_4dev", 4)):
        row = {"table": "fleet_scaling", "engine": name, "members": 4,
               "batch_size": 4, "updates_per_episode": 2,
               "devices": devices, "platform": out["platform"],
               "eps_per_s": round(out["eps"][name], 2)}
        if name == "fleet_4dev":
            row["dispatches_per_epoch"] = out["pop_epoch"]
            row["speedup_vs_1dev"] = round(
                out["eps"]["fleet_4dev"] / out["eps"]["fleet_1dev"], 2)
        rows.append(row)
    if verbose:
        print(f"[fleet_scaling] P=4 K=4 updates=2: "
              f"1dev {out['eps']['fleet_1dev']:.1f} eps/s, "
              f"4dev {out['eps']['fleet_4dev']:.1f} eps/s -> "
              f"{out['eps']['fleet_4dev'] / out['eps']['fleet_1dev']:.2f}x "
              f"(forced host devices share this box's single core)",
              flush=True)
    return rows


# ===========================================================================
# Serving throughput of the deployed compressed model (ISSUE 7)
# ===========================================================================

def serve_throughput_rows(batch: int = 4, steps: int = 32,
                          requests: int = 4, verbose: bool = True) -> list:
    """tokens/s the deployed tiny LM sustains under back-to-back batched
    decode requests, for uniform INT8 and INT4-weight policies — the
    end-to-end number the whole compression pipeline is for. Gated
    weekly (``serve_tok_per_s``, higher is better)."""
    from repro.core.policy import Policy
    from repro.core.spec import LayerCMP
    from repro.launch.serve import sustained_throughput
    cm, _ = _tiny_testbed()
    cfg = cm.cfg
    policies = {
        "serve_int8": Policy([LayerCMP(keep=s.prune_dim, mode="INT8",
                                       w_bits=8, a_bits=8)
                              for s in cm.specs]),
        "serve_int4": Policy([LayerCMP(keep=s.prune_dim, mode="MIX",
                                       w_bits=4, a_bits=8)
                              for s in cm.specs]),
    }
    rows = []
    for name, pol in policies.items():
        cspec = cm.build_cspec(pol)
        tok_s, times = sustained_throughput(
            cfg, cm.params, batch, steps, max_len=steps + 8, cspec=cspec,
            requests=requests)
        rows.append({"table": "serve", "engine": name,
                     "batch_size": batch, "steps": steps,
                     "requests": requests,
                     "serve_tok_per_s": round(tok_s, 1)})
        if verbose:
            print(f"[serve] {name}: {requests} requests x {batch}x{steps} "
                  f"tokens -> {tok_s:.1f} tok/s "
                  f"(per-request {min(times):.3f}-{max(times):.3f}s)",
                  flush=True)
    return rows


def main(out: str = "artifacts/bench_engine.json"):
    rows = (engine_comparison(updates=0) + engine_comparison(updates=8)
            + [calibrated_fused_row(), population_comparison()]
            + sensitivity_comparison()
            + update_floor_comparison()
            + serve_throughput_rows()
            + fleet_scaling_rows())
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"wrote {out}", flush=True)
    return rows


if __name__ == "__main__":
    main()
