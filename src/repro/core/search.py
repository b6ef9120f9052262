"""The Galen search loop (paper Fig. 1/2): episodes of layer-wise policy
prediction, hardware-oracle validation, and DDPG optimization.

Three agents (paper §Proposed Agents) share this loop and differ only in
``methods``:  "p" (pruning), "q" (quantization), "pq" (joint).

The engine
----------
``CompressionSearch`` runs episodes in batches of K (``batch_size``)
and whole batches in epochs of E (``epoch_batches``). An epoch
(``run_epoch``) is ONE ``jit`` program: a ``lax.scan`` over its E
batches whose body chains

* the rollout — a ``lax.scan`` over the L actionable units in which a
  traceable oracle (``JaxBatchOracle``) builds the latency features,
  ``agent_act_batch`` runs the actor with in-scan PRNG exploration,
  ``map_actions_batch`` projects the actions onto legal CMPs, and the
  (K, L) policy arrays ride in the carry (``make_rollout_fn``);
* the validation of the K policies (``accuracy_policy_fn``), the reward,
  the ``DeviceReplay`` ring write of the shared-episode-reward
  transitions, and the batch's DDPG update scan (``make_epoch_fn``).

The carry is ``(AgentState, ring, rollout PRNG, best-policy argmax)``;
the agent and ring buffers are donated so they update in place, and the
metrics come back as (E, K) arrays in one host readback per epoch.
Steady epochs share one update schedule and hence one compiled program.

The per-batch tail (``run_episode_batch``) runs one batch as separate
dispatches — rollout, validation, ring write, update chunk — and is
``run()``'s path for a trailing batch of fewer than K episodes. The
epoch program consumes the PRNG streams with the same split pattern, so
the per-batch tail run batch by batch reproduces an epoch draw for draw:
it is the reference ``tests/test_epoch.py`` compares the epoch against.
``dispatch_log`` names each dispatch ("epoch", or "rollout"/"validate"/
"push"/"update").

Learning
--------
Transitions live in a device-resident ``DeviceReplay``
(``core/replay.py``); a batch's critic/actor/target updates run as one
``lax.scan`` of ``update_step`` over the ``AgentState`` pytree
(``core/ddpg.py``), with replay sampling, reward moving-average
centering and state standardization inside the scan. Updates for the K
episodes of a batch run after the whole batch, and the state
normalizer's running stats advance once per batch, so episodes within a
batch act on the stats from the previous batch boundary.

Populations
-----------
``PopulationSearch`` stacks P member searches (p/q/pq agents, several
seeds, or one member per hardware target). With ``fuse_rollouts=True``
and members that share one trace (same specs, sensitivity, context,
methods, model and reward; hardware rates enter as a vmappable
``HwParams`` pytree) the P epochs run as ONE ``jit(vmap(epoch))``.
Members with different native action dimensionalities share a
population by padding ``action_dim`` to the maximum (``map_actions``
consumes a prefix of the action vector, so trailing entries are inert
for single-method agents). ``FleetSearch`` shards that population over
a device mesh and checkpoints its carry.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.checkpoint.checkpointing import (AsyncCheckpointer, restore_latest,
                                            save_async)
from repro.distributed.fault_tolerance import (FaultToleranceConfig,
                                               StepMonitor)
from repro.distributed.sharding import (pad_members, population_shardings,
                                       replicated)

from repro.core.constraints import legal_tables
from repro.core.ddpg import (_SCAN_UNROLL as _UPDATE_SCAN_UNROLL,
                             DDPGAgent, DDPGConfig, agent_act_batch,
                             chunk_sample_keys, observe_states_pure,
                             population_update_chunk, tree_index,
                             tree_stack, update_step)
from repro.core.latency import (V5E, HardwareTarget, LatencyContext,
                                fifo_cached, get_jax_oracle, policy_latency)
from repro.core.policy import (Policy, PolicyBatch, action_columns,
                               map_actions_batch, n_actions,
                               policies_from_batch, stack_policies)
from repro.core.replay import (DeviceReplay, device_replay_push,
                               device_replay_sample)
from repro.core.reward import RewardConfig, compute_reward_batch
from repro.core.sensitivity import SensitivityResult, run_sensitivity
from repro.core import spans
from repro.core.state import StateTables, fused_state_block, state_dim


@dataclass(frozen=True)
class SearchConfig:
    methods: str = "pq"                # p | q | pq
    episodes: int = 120
    reward: RewardConfig = field(default_factory=RewardConfig)
    ddpg: Optional[DDPGConfig] = None  # None -> sized to the method set
    seed: int = 0
    window: int = 0                    # attention window for the oracle
    track_bops: bool = True
    # latency oracle flavor (core/measure.py):
    #   analytic   — pure roofline (the default, zero measurement deps)
    #   calibrated — roofline terms rescaled by the fitted per-(kind,
    #                container) factors; stays fully traced/batched
    #   measured   — calibrated search + wall-clock re-timing of the
    #                top-K final candidates (SearchResult.measured)
    oracle_mode: str = "analytic"
    calibration_path: str = ""         # "" -> artifacts/latency_calibration.json
    measure_top_k: int = 3             # distinct candidates re-timed


@dataclass
class EpisodeRecord:
    episode: int
    reward: float
    accuracy: float
    latency_s: float
    latency_ratio: float
    macs_frac: float
    bops: float
    sigma: float
    policy: Policy = field(repr=False, default=None)


@dataclass
class SearchResult:
    history: List[EpisodeRecord]
    best: EpisodeRecord
    ref_latency_s: float
    ref_accuracy: float
    # oracle_mode="measured": wall-clock rows for the top-K candidates
    # (predicted vs measured seconds and ratios vs the reference model)
    measured: Optional[List[dict]] = None

    def best_under_budget(self, tol: float = 0.05) -> Optional[EpisodeRecord]:
        c = None
        for r in self.history:
            if r.latency_ratio <= (1.0 + tol):
                if c is None or r.accuracy > c.accuracy:
                    c = r
        return c


def _actionable(spec, methods: str) -> bool:
    if methods == "p":
        return spec.prunable and spec.prune_dim > 0
    if methods == "q":
        return spec.quantizable
    return spec.quantizable or (spec.prunable and spec.prune_dim > 0)



# ===========================================================================
# The rollout environment as one jit(lax.scan)
# ===========================================================================

class MethodCols(NamedTuple):
    """Which action columns feed pruning/quantization, and whether each
    method is live — as traced values, so the rollout step function is
    method-agnostic (one compiled form serves p/q/pq and the columns
    vmap across a population)."""
    ip: jnp.ndarray            # () i32  prune-ratio action column
    iw: jnp.ndarray            # () i32  weight-bits action column
    ia: jnp.ndarray            # () i32  act-bits action column
    do_p: jnp.ndarray          # () bool method prunes
    do_q: jnp.ndarray          # () bool method quantizes


def method_cols(methods: str) -> MethodCols:
    ip, iw, ia = action_columns(methods)
    return MethodCols(
        ip=jnp.asarray(ip, jnp.int32), iw=jnp.asarray(iw, jnp.int32),
        ia=jnp.asarray(ia, jnp.int32),
        do_p=jnp.asarray("p" in methods), do_q=jnp.asarray("q" in methods))


def make_rollout_fn(cfg: DDPGConfig, oracle, legal, static_tab, spec_steps):
    """Build the pure rollout function the engine's epoch program scans
    (and its per-batch tail jits; a population ``jit(vmap)``s it).

    Closure constants: the agent config, the traceable oracle (specs/
    context tables; hardware rates stay in the ``hwp`` argument), the
    legality tables, the (T, S) static feature rows, and the (T,) spec
    index per step. Everything hardware- or member-specific is an
    argument so one traced function serves a vmapped stack of members.

    Returns ``rollout(st, keep0, wb0, ab0, sigmas, warmup, hwp, shares,
    ref_total, cols, keys) -> (keep, wb, ab, states, actions, lats)``
    with ``states``/``actions`` stacked (T, K, ·) in step order and
    ``lats`` the final policies' oracle latency — the whole episode
    environment in one dispatch.
    """
    pd = jnp.asarray(legal.prune_dim)
    gran = jnp.asarray(legal.granularity)
    prunable = jnp.asarray(legal.prunable)
    quantizable = jnp.asarray(legal.quantizable)
    mix_ok = jnp.asarray(legal.mix_ok)
    static_tab = jnp.asarray(static_tab)
    spec_steps = jnp.asarray(spec_steps)

    def rollout(st, keep0, wb0, ab0, sigmas, warmup, hwp, shares,
                ref_total, cols, keys):
        K = sigmas.shape[0]
        L = keep0.shape[-1]
        init = (jnp.broadcast_to(keep0, (K, L)),
                jnp.broadcast_to(wb0, (K, L)),
                jnp.broadcast_to(ab0, (K, L)),
                jnp.zeros((K, cfg.action_dim), jnp.float32))

        def step(carry, x):
            keep, wb, ab, prev_a = carry
            t, static_row, share_row, k = x
            unit_t, extra_t = oracle.unit_times(keep, wb, ab, hwp)
            decided = oracle.decided_before(unit_t, extra_t, t) / ref_total
            S = fused_state_block(static_row, share_row, decided, prev_a)
            A = agent_act_batch(cfg, st, S, k, sigmas, warmup)
            new_keep, new_wb, new_ab = map_actions_batch(
                A, prune_dim=pd[t], granularity=gran[t],
                prunable=prunable[t], quantizable=quantizable[t],
                mix_ok=mix_ok[t], ip=cols.ip, iw=cols.iw, ia=cols.ia)
            # single-method agents preserve the other method's reference
            # parameters (a frozen stage-1 policy can be the starting
            # point of the sequential scheme, paper App. A)
            keep = keep.at[:, t].set(
                jnp.where(cols.do_p, new_keep, keep[:, t]))
            wb = wb.at[:, t].set(jnp.where(cols.do_q, new_wb, wb[:, t]))
            ab = ab.at[:, t].set(jnp.where(cols.do_q, new_ab, ab[:, t]))
            return (keep, wb, ab, A), (S, A)

        xs = (spec_steps, static_tab, shares, keys)
        (keep, wb, ab, _), (states, actions) = jax.lax.scan(step, init, xs)
        unit_t, extra_t = oracle.unit_times(keep, wb, ab, hwp)
        lats = oracle.totals(unit_t, extra_t, hwp)
        return keep, wb, ab, states, actions, lats

    return rollout


# ===========================================================================
# The epoch program: E episode batches as one jit(lax.scan)
# ===========================================================================

def _schedule_segments(schedule: tuple) -> List[tuple]:
    """Group a static update schedule into (n_updates, batch count)
    runs of consecutive equal entries: (32, 64, 64, 64) -> [(32, 1),
    (64, 3)]. Each run becomes its own scan with an UNMASKED inner
    update scan of exactly n steps — no wasted masked GEMMs, no
    per-step tree selects, and the same op sequence as the per-batch
    ``update_chunk``. Steady-state epochs are one segment."""
    segs: List[tuple] = []
    for n in schedule:
        if segs and segs[-1][0] == n:
            segs[-1] = (n, segs[-1][1] + 1)
        else:
            segs.append((n, 1))
    return segs


def make_epoch_fn(cfg: DDPGConfig, reward_cfg: RewardConfig, rollout_fn,
                  acc_fn, T: int, K: int, schedule: tuple):
    """Build the pure epoch function: E = len(schedule) episode batches
    as one traced program — a ``lax.scan`` per schedule segment whose
    body chains the fused rollout, the traced-cspec validation
    (``acc_fn``), the reward, the replay ring write, and the update
    scan as carry transitions over ``(AgentState, DeviceReplayData,
    rollout PRNG key, best)``.

    ``schedule`` is the STATIC per-batch fused-update step count (see
    ``CompressionSearch._update_schedule``): the update-sampling
    keys every batch will consume are derived at trace time with the
    exact ``chunk_sample_keys`` splits the per-batch path performs —
    ``jax.random.split`` is not prefix-stable across lengths, so a
    traced count could not reproduce them — and consecutive equal
    counts share one scan (``_schedule_segments``), so every batch runs
    exactly its budget. Steady-state epochs all share one schedule,
    hence one compiled executable (FIFO-cached by the engine).
    Everything member-specific is an argument, so a population can
    ``jit(vmap)`` one epoch function across stacked members.

    Returns ``epoch(params, st, ring, rkey, keep0, wb0, ab0, sigmas,
    warmup, hwp, shares, ref_total, cols, ref_total_s) -> (st, ring,
    rkey, best, ys)``. ``params`` are the model weights ``acc_fn``
    validates with (an argument, so no program embeds them);
    ``ys = (accs, lats, rewards, keep, wb, ab)`` is
    stacked (E, ...) — the device-side metrics read back in one
    transfer — and ``best = (reward, episode offset, (keep, wb, ab))``
    the in-carry argmax over the epoch's E*K episodes.
    """
    segments = _schedule_segments(schedule)

    def epoch(params, st, ring, rkey, keep0, wb0, ab0, sigmas, warmup,
              hwp, shares, ref_total, cols, ref_total_s):
        # trace-time sample-key schedule (zero runtime dispatches):
        # consume st.key exactly as E per-batch update_chunk calls would
        key = st.key
        seg_keys = []
        for n, cnt in segments:
            if n > 0:
                ks = []
                for _ in range(cnt):
                    key, sk = chunk_sample_keys(key, n)
                    ks.append(sk)
                seg_keys.append(jnp.stack(ks))      # (cnt, n, key)
            else:
                seg_keys.append(None)
        final_key = key

        def make_body(n):
            def body(carry, x):
                st, ring, rk, best = carry
                (e, sig, warm), skeys = x[:3], (x[3] if n > 0 else None)
                rk, bk = jax.random.split(rk)
                keys = jax.random.split(bk, T)
                # the named scopes tag each stage's device ops in the
                # profiler trace (metadata only; the ops are the same)
                with jax.named_scope("rollout"):
                    keep, wb, ab, states, actions, lats = rollout_fn(
                        st, keep0, wb0, ab0, sig, warm, hwp, shares,
                        ref_total, cols, keys)
                    # the normalizer advances at the batch boundary,
                    # exactly as the per-batch tail's observe_states does
                    st = observe_states_pure(st, states.reshape(T * K, -1))
                with jax.named_scope("validation"):
                    accs = acc_fn(params, keep.astype(jnp.int32),
                                  wb.astype(jnp.int32),
                                  ab.astype(jnp.int32))
                with jax.named_scope("reward"):
                    rewards = compute_reward_batch(reward_cfg, accs, lats,
                                                   ref_total_s)
                order = lambda z: jnp.swapaxes(z, 0, 1).reshape(
                    T * K, *z.shape[2:])
                with jax.named_scope("replay_push"):
                    nxt = jnp.concatenate([states[1:], states[-1:]])
                    done = jnp.zeros((T, K), jnp.float32).at[-1].set(1.0)
                    ring = device_replay_push(
                        ring, order(states), order(actions),
                        jnp.repeat(rewards, T).astype(jnp.float32),
                        order(nxt), order(done))
                if n > 0:     # this batch's update chunk, in-scan
                    def ustep(c, k2):
                        batch = device_replay_sample(ring, k2,
                                                     cfg.batch_size)
                        return update_step(cfg, c, batch)

                    with jax.named_scope("update"):
                        st, _losses = jax.lax.scan(
                            ustep, st, skeys,
                            unroll=min(_UPDATE_SCAN_UNROLL, n))
                # in-carry best-policy tracking; strict > keeps the
                # earliest argmax, the rule run()'s host loop applies
                j = jnp.argmax(rewards)
                better = rewards[j] > best[0]
                pick = lambda a, b: jnp.where(better, a, b)
                best = (pick(rewards[j], best[0]),
                        pick(e * K + j, best[1]),
                        jax.tree.map(pick, (keep[j], wb[j], ab[j]),
                                     best[2]))
                return (st, ring, rk, best), (accs, lats, rewards, keep,
                                              wb, ab)

            return body

        L = keep0.shape[-1]
        best0 = (jnp.asarray(-jnp.inf, jnp.float32),
                 jnp.zeros((), jnp.int32),
                 tuple(jnp.zeros((L,), jnp.float32) for _ in range(3)))
        carry = (st, ring, rkey, best0)
        outs, base = [], 0
        for (n, cnt), sk in zip(segments, seg_keys):
            xs = (jnp.arange(base, base + cnt, dtype=jnp.int32),
                  sigmas[base:base + cnt], warmup[base:base + cnt])
            if n > 0:
                xs = xs + (sk,)
            carry, ys = jax.lax.scan(make_body(n), carry, xs)
            outs.append(ys)
            base += cnt
        st, ring, rk, best = carry
        ys = outs[0] if len(outs) == 1 else jax.tree.map(
            lambda *zs: jnp.concatenate(zs, axis=0), *outs)
        return st._replace(key=final_key), ring, rk, best, ys

    return epoch


_EPOCH_CACHE_MAX = 16


class CompressionSearch:
    """The search engine: owns the compressible model, the sensitivity
    table, the latency oracle, the agent and its replay ring, and runs
    the episode loop as epoch programs (see the module docstring).

    Exploration randomness comes from a dedicated jax PRNG stream
    (``seed``-derived, separate from the agent's update-sampling key);
    ``_last_batch_key`` exposes the per-batch tail's key so parity tests
    can replay its exact draws through a host reference.
    """

    def __init__(self, cmodel, val_batch, search_cfg: SearchConfig,
                 ctx: LatencyContext, hw: HardwareTarget = V5E,
                 sens: Optional[SensitivityResult] = None,
                 calib_batch=None, calib=None, batch_size: int = 8,
                 epoch_batches: int = 1):
        if epoch_batches < 1:
            raise ValueError(
                f"epoch_batches must be >= 1 (whole batches run as epoch "
                f"programs), got {epoch_batches}")
        self.cmodel = cmodel
        self.specs = cmodel.specs
        self.cfg = search_cfg
        self.hw = hw
        self.ctx = ctx
        self.val_batch = val_batch
        # latency-oracle flavor: a CalibrationTable rescales every oracle
        # form's terms in calibrated/measured mode; analytic ignores it
        mode = search_cfg.oracle_mode
        if mode not in ("analytic", "calibrated", "measured"):
            raise ValueError(
                f"SearchConfig.oracle_mode must be analytic|calibrated|"
                f"measured, got {mode!r}")
        if mode != "analytic" and calib is None:
            from repro.core.measure import load_calibration
            calib = load_calibration(search_cfg.calibration_path or None)
        self.calib = calib if mode != "analytic" else None
        native = n_actions(search_cfg.methods)
        ddpg_cfg = search_cfg.ddpg or DDPGConfig(
            state_dim=state_dim(native), action_dim=native)
        # a provided action_dim larger than the method's native one pads
        # the action space (population members must share shapes); a
        # smaller one is corrected up to native
        a_dim = max(native, ddpg_cfg.action_dim)
        if (ddpg_cfg.state_dim, ddpg_cfg.action_dim) != (state_dim(a_dim),
                                                         a_dim):
            ddpg_cfg = DDPGConfig(**{**ddpg_cfg.__dict__,
                                     "state_dim": state_dim(a_dim),
                                     "action_dim": a_dim})
        self.agent = DDPGAgent(ddpg_cfg, seed=search_cfg.seed)
        self.replay = DeviceReplay(ddpg_cfg.buffer_size, ddpg_cfg.state_dim,
                                   a_dim, seed=search_cfg.seed)
        # fused + memoized (ONE jit execution for the whole layer×probe
        # grid, shared across every engine built on the same model and
        # calibration batch — population members included)
        self.sens = sens if sens is not None else run_sensitivity(
            cmodel, calib_batch if calib_batch is not None else val_batch)
        self._jit_acc = jax.jit(
            lambda p, cs: cmodel.accuracy(val_batch, cs, p))
        self.ref_policy = Policy.reference(self.specs)
        self.ref_lat = policy_latency(self.specs, self.ref_policy, hw, ctx,
                                      search_cfg.window, calib=self.calib)
        self.ref_acc = float(self._jit_acc(
            cmodel.params, cmodel.build_cspec(self.ref_policy)))
        self.steps = [i for i, s in enumerate(self.specs)
                      if _actionable(s, search_cfg.methods)]
        self._pending_updates = 0
        self._defer_updates = False     # PopulationSearch batches flushes
        self.batch_size = max(1, batch_size)
        # calibration factors enter the traced oracle as constants
        self.oracle = get_jax_oracle(self.specs, hw, ctx, search_cfg.window,
                                     calib=self.calib)
        self.tables = StateTables(self.specs, self.steps, self.sens,
                                  self.ref_lat)
        ref_pb = stack_policies(self.specs, [self.ref_policy])
        self._ref_rows = tuple(
            jnp.asarray(x[0], jnp.float32)
            for x in (ref_pb.keep, ref_pb.w_bits, ref_pb.a_bits))
        self._cols = method_cols(search_cfg.methods)
        self._rollout_fn = make_rollout_fn(
            self.agent.cfg, self.oracle, legal_tables(self.specs),
            self.tables.static, self.tables.spec_idx)
        self._rollout = jax.jit(self._rollout_fn)
        self._rollout_key = jax.random.PRNGKey(search_cfg.seed + 0x5EED)
        self._last_batch_key = None
        self.dispatch_log: List[str] = []
        self.epoch_batches = epoch_batches
        self._epoch_cache: dict = {}
        self.last_epoch_best: Optional[tuple] = None
        spans.watch_gc()

    # ------------------------------------------------------------------
    def _flush_updates(self):
        """Dispatch the accumulated update budget as one fused chunk."""
        n = self._pending_updates
        self._pending_updates = 0
        if n > 0 and len(self.replay) >= self.agent.cfg.batch_size:
            self.dispatch_log.append("update")
            self.agent.update_chunk(self.replay, n)

    def _queue_updates(self, n: int):
        self._pending_updates += n
        if not self._defer_updates:
            self._flush_updates()

    def _batch_schedule(self, first_episode: int, k: int):
        """(warmup mask, sigma) per episode row — THE one place the
        batch's exploration schedule is derived (rollout and
        finish/record paths must agree on it)."""
        eps = range(first_episode, first_episode + k)
        warmup = np.asarray(
            [e < self.agent.cfg.warmup_episodes for e in eps])
        sigmas = np.asarray([self.agent.sigma_at(e) for e in eps],
                            np.float32)
        return warmup, sigmas

    # ---------------------------------------------------- per-batch tail
    def _rollout_args(self, first_episode: int, k: int) -> tuple:
        """Per-batch argument tuple for ``_rollout_fn`` (every element
        stackable across population members); advances the rollout PRNG
        stream."""
        warmup, sigmas = self._batch_schedule(first_episode, k)
        self._rollout_key, bk = jax.random.split(self._rollout_key)
        self._last_batch_key = bk
        keys = jax.random.split(bk, len(self.steps))
        keep0, wb0, ab0 = self._ref_rows
        return (self.agent.state_for_dispatch(), keep0, wb0, ab0,
                jnp.asarray(sigmas), jnp.asarray(warmup), self.oracle.hwp,
                jnp.asarray(self.tables.shares),
                jnp.asarray(self.tables.ref_total, jnp.float32),
                self._cols, keys)

    def run_episode_batch(self, first_episode: int,
                          k: int) -> List[EpisodeRecord]:
        """One batch of ``k`` episodes as separate dispatches: rollout,
        validation, ring write, update chunk."""
        args = self._rollout_args(first_episode, k)
        out = self._rollout(*args)
        self.dispatch_log.append("rollout")
        return self._finish_batch(first_episode, k, out)

    def _finish_batch(self, first_episode: int, k: int,
                      out: tuple) -> List[EpisodeRecord]:
        """Validation, reward, replay write, records — everything after
        the rollout dispatch. ``out`` is a ``_rollout_fn`` result."""
        cfg = self.cfg
        keep, wb, ab, dev_states, dev_actions, lats = out
        eps = list(range(first_episode, first_episode + k))
        warmup, sigmas = self._batch_schedule(first_episode, k)
        pb = PolicyBatch(keep=np.asarray(keep, np.float64),
                         w_bits=np.asarray(wb, np.float64),
                         a_bits=np.asarray(ab, np.float64))
        accs = np.asarray(
            self.cmodel.accuracy_policy_batch(self.val_batch, pb))
        self.dispatch_log.append("validate")
        lats = np.asarray(lats, np.float64)
        rewards = np.asarray(compute_reward_batch(
            cfg.reward, accs.astype(np.float32),
            lats.astype(np.float32), self.ref_lat.total_s), np.float64)
        return self._push_and_record(
            eps, warmup, sigmas, policies_from_batch(self.specs, pb),
            np.asarray(dev_states), np.asarray(dev_actions), accs, lats,
            rewards)

    def _push_and_record(self, eps, warmup, sigmas, pols, states,
                         actions, accs, lats,
                         rewards) -> List[EpisodeRecord]:
        """The per-batch tail's shared-episode-reward transition scheme
        (the epoch scan writes the same ring rows): observe the
        (T, K, ·) states, push per-episode chains as one bulk ring write
        (reward repeated along each chain, done on the last step), queue
        the live episodes' update budget, and build the records."""
        cfg = self.cfg
        T, k = len(self.steps), len(eps)
        self.agent.observe_states(states.reshape(T * k, -1))
        nxt = np.concatenate([states[1:], states[-1:]])
        done = np.zeros((T, k), np.float32)
        done[-1] = 1.0
        order = lambda x: x.swapaxes(0, 1).reshape(T * k, *x.shape[2:])
        self.replay.push_batch(
            order(states), order(actions),
            np.repeat(rewards, T).astype(np.float32),
            order(nxt), order(done))
        self.dispatch_log.append("push")
        n_live = int((~warmup).sum())
        self._queue_updates(self.agent.cfg.updates_per_episode * n_live)

        # record tail: ONE bulk conversion per batch (a single
        # np.asarray readback each), not per-episode scalar float()s
        acc_l, lat_l, rew_l, sig_l = (
            np.asarray(x, np.float64).tolist()
            for x in (accs, lats, rewards, sigmas))
        denom = cfg.reward.target_ratio * self.ref_lat.total_s
        records = []
        for j, e in enumerate(eps):
            records.append(EpisodeRecord(
                episode=e, reward=rew_l[j],
                accuracy=acc_l[j], latency_s=lat_l[j],
                latency_ratio=lat_l[j] / denom,
                macs_frac=pols[j].macs_fraction(self.specs),
                bops=pols[j].bops(self.specs) if cfg.track_bops else 0.0,
                sigma=sig_l[j], policy=pols[j]))
        return records

    # ------------------------------------------------------------ epochs
    def _update_schedule(self, first_episode: int,
                         n_batches: int) -> tuple:
        """Per-batch fused-update step counts for an epoch, as a STATIC
        tuple — exactly the budgets ``_queue_updates``/``_flush_updates``
        would dispatch batch by batch. Warmup positions come from the
        episode indices and the replay-fill gate from the host size
        mirror (pushes per batch are fixed at T*K), so the whole
        schedule is known before the dispatch; it must be, because the
        epoch trace derives its update-sampling keys from it."""
        K, T = self.batch_size, len(self.steps)
        cfg = self.agent.cfg
        size, cap = self.replay.size, self.replay.capacity
        sched = []
        for e in range(n_batches):
            warmup, _ = self._batch_schedule(first_episode + e * K, K)
            n = cfg.updates_per_episode * int((~warmup).sum())
            size = min(size + T * K, cap)
            sched.append(n if (n > 0 and size >= cfg.batch_size) else 0)
        return tuple(sched)

    def _epoch_args(self, first_episode: int, n_batches: int) -> tuple:
        """Per-epoch argument tuple for the ``make_epoch_fn`` callable
        (every element stackable across population members). Unlike
        ``_rollout_args`` this does NOT advance the rollout PRNG on the
        host — the scan splits it per batch and the engine adopts the
        final carry."""
        K = self.batch_size
        scheds = [self._batch_schedule(first_episode + e * K, K)
                  for e in range(n_batches)]
        warm = np.stack([w for w, _ in scheds])
        sig = np.stack([s for _, s in scheds])
        keep0, wb0, ab0 = self._ref_rows
        return (self.agent.state_for_dispatch(), self.replay.data,
                self._rollout_key, keep0, wb0, ab0,
                jnp.asarray(sig), jnp.asarray(warm), self.oracle.hwp,
                jnp.asarray(self.tables.shares),
                jnp.asarray(self.tables.ref_total, jnp.float32),
                self._cols,
                jnp.asarray(self.ref_lat.total_s, jnp.float32))

    def _make_epoch_fn(self, schedule: tuple):
        """The pure epoch function for this engine and schedule (the
        population vmaps the same construction)."""
        return make_epoch_fn(
            self.agent.cfg, self.cfg.reward, self._rollout_fn,
            self.cmodel.accuracy_policy_fn(self.val_batch),
            len(self.steps), self.batch_size, schedule)

    def _epoch_fn_for(self, schedule: tuple):
        """Compiled epoch executable, FIFO-cached per schedule (steady-
        state epochs all share one schedule => one compilation). Agent
        state and ring buffers are donated: they update in place and the
        pre-dispatch pytrees become invalid — the engine adopts the
        outputs immediately."""
        params = self.cmodel.params
        hit = fifo_cached(
            self._epoch_cache, _EPOCH_CACHE_MAX,
            (self.batch_size, schedule, id(params)),
            lambda h: h[0] is params,
            lambda: (params, jax.jit(self._make_epoch_fn(schedule),
                                     donate_argnums=(1, 2))))
        return hit[1]

    def run_epoch(self, first_episode: int,
                  n_batches: int) -> List[EpisodeRecord]:
        """E episode batches — rollout, validation, reward, ring write,
        updates, metrics — as ONE jit execution, then ONE host readback
        that rehydrates the records in bulk. Each host phase is a
        ``search.epoch.*`` span (``core/spans.py``)."""
        if n_batches <= 0:
            return []
        with spans.span("search.epoch", first_episode=first_episode):
            with spans.span("search.epoch.args",
                            first_episode=first_episode):
                self._flush_updates()   # epoch budgets are computed fresh
                schedule = self._update_schedule(first_episode, n_batches)
                fn = self._epoch_fn_for(schedule)
                args = self._epoch_args(first_episode, n_batches)
            with spans.span("search.epoch.dispatch",
                            first_episode=first_episode):
                out = fn(self.cmodel.params, *args)
            self.dispatch_log.append("epoch")
            return self._finish_epoch(first_episode, n_batches, out)

    def _finish_epoch(self, first_episode: int, n_batches: int,
                      out: tuple) -> List[EpisodeRecord]:
        """Adopt the carried state/ring/PRNG, do the epoch's single
        device->host transfer, and build the records."""
        K, T = self.batch_size, len(self.steps)
        st, ring, rkey, best, ys = out
        self.replay.adopt(ring, n_batches * T * K)
        self._rollout_key = rkey
        self.agent.adopt_state(st)
        accs, lats, rewards, keep, wb, ab = ys
        # THE one host readback per epoch: metrics, policies, the norm
        # stats, and the in-carry best — records need no device values
        host = (accs, lats, rewards, keep, wb, ab,
                (st.norm_count, st.norm_mean, st.norm_var),
                (best[0], best[1]))
        # the wait is its own span, so the readback span is the copy
        with spans.span("search.epoch.wait", first_episode=first_episode):
            jax.block_until_ready(host)
        with spans.span("search.epoch.readback",
                        first_episode=first_episode):
            got = jax.device_get(host)
        with spans.span("search.epoch.records",
                        first_episode=first_episode):
            return self._epoch_records(first_episode, n_batches, got)

    def _epoch_records(self, first_episode: int, n_batches: int,
                       got: tuple) -> List[EpisodeRecord]:
        """The epoch's ``EpisodeRecord``s from its host readback."""
        cfg = self.cfg
        K = self.batch_size
        accs, lats, rewards, keep, wb, ab, norm, best_hv = got
        self.agent.norm.count = float(norm[0])
        self.agent.norm.mean = np.asarray(norm[1], np.float32)
        self.agent.norm.var = np.asarray(norm[2], np.float32)
        self.last_epoch_best = (first_episode + int(best_hv[1]),
                                float(best_hv[0]))
        denom = cfg.reward.target_ratio * self.ref_lat.total_s
        records = []
        for e in range(n_batches):
            _, sigmas = self._batch_schedule(first_episode + e * K, K)
            pb = PolicyBatch(keep=np.asarray(keep[e], np.float64),
                             w_bits=np.asarray(wb[e], np.float64),
                             a_bits=np.asarray(ab[e], np.float64))
            pols = policies_from_batch(self.specs, pb)
            acc_l, lat_l, rew_l = (
                np.asarray(x, np.float64).tolist()
                for x in (accs[e], lats[e], rewards[e]))
            for j in range(K):
                records.append(EpisodeRecord(
                    episode=first_episode + e * K + j, reward=rew_l[j],
                    accuracy=acc_l[j], latency_s=lat_l[j],
                    latency_ratio=lat_l[j] / denom,
                    macs_frac=pols[j].macs_fraction(self.specs),
                    bops=pols[j].bops(self.specs) if cfg.track_bops
                    else 0.0,
                    sigma=float(sigmas[j]), policy=pols[j]))
        return records

    # ------------------------------------------------------------ the loop
    def _chunk_size(self) -> int:
        return self.batch_size * self.epoch_batches

    def _run_chunk(self, first_episode: int,
                   k: int) -> List[EpisodeRecord]:
        nb, rem = divmod(k, self.batch_size)
        recs = self.run_epoch(first_episode, nb)
        if rem:           # trailing partial batch: the per-batch tail
            recs += self.run_episode_batch(
                first_episode + nb * self.batch_size, rem)
        return recs

    def run(self, episodes: Optional[int] = None,
            verbose: bool = False) -> SearchResult:
        n = episodes or self.cfg.episodes
        history: List[EpisodeRecord] = []
        best = None
        e = 0
        while e < n:
            k = min(self._chunk_size(), n - e)
            for rec in self._run_chunk(e, k):
                history.append(rec)
                if best is None or rec.reward > best.reward:
                    best = rec
                if verbose and (rec.episode % 10 == 0
                                or rec.episode == n - 1):
                    print(f"  ep {rec.episode:4d} reward={rec.reward:+.4f} "
                          f"acc={rec.accuracy:.3f} "
                          f"lat_ratio={rec.latency_ratio:.3f} "
                          f"sigma={rec.sigma:.3f}")
            e += k
        result = SearchResult(history=history, best=best,
                              ref_latency_s=self.ref_lat.total_s,
                              ref_accuracy=self.ref_acc)
        if self.cfg.oracle_mode == "measured":
            result.measured = self._measure_top_k(history)
        return result

    def _measure_top_k(self, history: List[EpisodeRecord]) -> List[dict]:
        """Wall-clock the deployed forward of the top-K candidates (the
        paper's measure-on-target step, applied only to finalists). The
        measurement memo is FIFO-cached by container signature, so
        candidates sharing a deployment are timed once."""
        from repro.core import measure
        k = max(1, self.cfg.measure_top_k)
        top = sorted(history, key=lambda r: r.reward, reverse=True)[:k]
        ref_s = measure.measure_policy(self.cmodel, self.ref_policy,
                                       self.val_batch)
        rows = []
        for r in top:
            t = measure.measure_policy(self.cmodel, r.policy,
                                       self.val_batch)
            rows.append({
                "episode": r.episode, "reward": r.reward,
                "predicted_s": r.latency_s,
                "predicted_ratio": r.latency_s / self.ref_lat.total_s,
                "measured_s": t, "measured_ref_s": ref_s,
                "measured_ratio": t / ref_s if ref_s > 0 else float("inf"),
            })
        return rows


# The benchmark's driver imports the engine by the name it had when the
# epoch program was one of several engine classes.
FusedCompressionSearch = CompressionSearch


class PopulationSearch:
    """P member searches run in lockstep.

    This is the paper's actual workload shape: the p/q/pq agents (and,
    for hardware-specific policies, one member per target) search
    concurrently. With ``fuse_rollouts=True`` and members that share one
    epoch trace (``_epochs_fusable``: same specs/sensitivity/context/
    methods, hence the same step list, and the same model, validation
    batch and reward — the multi-hardware-target scenario, or multiple
    seeds), every epoch of all P members is ONE ``jit(vmap(epoch))``
    over the stacked agent states, rings, PRNG keys and per-target
    ``HwParams``/latency-share arguments. Otherwise each member runs its
    own epoch programs.

    A trailing partial batch runs through the members' per-batch tails:
    their rollouts share one ``jit(vmap(rollout))`` when
    ``_rollouts_fusable``, and their update budgets are dispatched as
    one population update chunk when they agree (per-member flushes
    otherwise).

    Requirements: members must share one ``DDPGConfig`` (pad
    ``action_dim`` to the population maximum for mixed-method
    populations; see the module docstring) and one chunk size.

    Construction cost: members built on a common model + calibration
    batch share ONE sensitivity analysis — ``run_sensitivity`` is fused
    (one jit execution for the whole layer×probe grid) and memoized per
    (cmodel, batch, params) identity, so the population constructor
    pays the analysis once, not P times (and shared traces require the
    shared table anyway — see ``_rollouts_fusable``).
    """

    def __init__(self, members: Sequence[CompressionSearch],
                 fuse_rollouts: bool = False):
        if not members:
            raise ValueError("PopulationSearch needs at least one member")
        self.members = list(members)
        cfg0 = self.members[0].agent.cfg
        for m in self.members[1:]:
            if m.agent.cfg != cfg0:
                raise ValueError(
                    "population members must share a DDPGConfig (pad "
                    f"action_dim): {m.agent.cfg} != {cfg0}")
        if len({m._chunk_size() for m in self.members}) != 1:
            raise ValueError("population members must share a chunk size")
        self.fuse_rollouts = fuse_rollouts
        self._pop_rollout = None
        self._fusable = None
        self._pop_epoch_cache: dict = {}
        self._epoch_fusable = None

    def _stack_for_dispatch(self, trees):
        """Stack per-member pytrees (arg tuples, agent states, rings)
        along a new leading member axis for a shared dispatch.
        ``FleetSearch`` overrides this to pad the member axis up to the
        mesh ``data`` extent and commit the stack to the mesh, which
        makes every shared dispatch run one member per device."""
        return tree_stack(trees)

    def _params_for_dispatch(self, params):
        """The shared model weights as a shared dispatch takes them
        (unbatched); ``FleetSearch`` replicates them over its mesh."""
        return params

    def _member_map(self, fn, in_axes=0):
        """``fn`` mapped over the stacked member axis of its arguments
        (``in_axes`` None marks an argument all members share).
        ``FleetSearch`` runs the map per device under ``shard_map``."""
        return jax.vmap(fn, in_axes=in_axes)

    def _rollouts_fusable(self) -> bool:
        """One vmapped rollout needs one traced step function: same spec
        list (identity — the oracle/legal/static tables bake into the
        trace), same sensitivity table, same context/window/methods (the
        step lists must coincide), same MXU alignment. Hardware rates
        and latency shares are arguments, so targets may differ."""
        if self._fusable is None:
            ms = self.members
            m0 = ms[0]
            self._fusable = all(
                m.specs is m0.specs and m.sens is m0.sens
                and m.ctx == m0.ctx
                and m.cfg.window == m0.cfg.window
                and m.cfg.methods == m0.cfg.methods
                and m.hw.mxu_align == m0.hw.mxu_align
                and m.calib is m0.calib
                for m in ms[1:])
        return self._fusable

    def _run_fused_chunk(self, first_episode: int,
                         k: int) -> List[List[EpisodeRecord]]:
        """All members' rollouts as ONE vmapped dispatch, then the
        per-member validation/replay/record tail."""
        args = [m._rollout_args(first_episode, k) for m in self.members]
        stacked = self._stack_for_dispatch(args)
        if self._pop_rollout is None:
            self._pop_rollout = jax.jit(
                self._member_map(self.members[0]._rollout_fn))
        outs = self._pop_rollout(*stacked)
        for m in self.members:     # ONE shared dispatch, logged on each
            m.dispatch_log.append("rollout")
        return [m._finish_batch(first_episode, k, tree_index(outs, i))
                for i, m in enumerate(self.members)]

    # ------------------------------------------------------------ epochs
    def _epochs_fusable(self) -> bool:
        """A shared epoch dispatch bakes the validator and the reward
        into one trace on top of the rollout requirements: members must
        share the compressible model, the validation batch, and the
        reward config (the per-target reference-latency scale stays an
        argument)."""
        if self._epoch_fusable is None:
            ms = self.members
            m0 = ms[0]
            self._epoch_fusable = self._rollouts_fusable() and \
                all(m.cmodel is m0.cmodel
                    and m.val_batch is m0.val_batch
                    and m.cfg.reward == m0.cfg.reward for m in ms[1:])
        return self._epoch_fusable

    def run_epoch(self, first_episode: int,
                  n_batches: int) -> List[List[EpisodeRecord]]:
        """All members' epochs as ONE vmapped jit execution — E batches
        x P members of rollout+validate+push+update in a single
        dispatch. Members whose update schedules diverge (they ran
        different histories) fall back to per-member epoch dispatches.
        """
        if n_batches <= 0:
            return [[] for _ in self.members]
        with spans.span("search.epoch", first_episode=first_episode):
            with spans.span("search.epoch.args",
                            first_episode=first_episode):
                for m in self.members:
                    m._flush_updates()
                scheds = {m._update_schedule(first_episode, n_batches)
                          for m in self.members}
                fused = len(scheds) == 1 and self._epochs_fusable()
                if fused:
                    fn, args = self._epoch_call(first_episode, n_batches,
                                                next(iter(scheds)))
            if not fused:
                return [m.run_epoch(first_episode, n_batches)
                        for m in self.members]
            with spans.span("search.epoch.dispatch",
                            first_episode=first_episode):
                outs = fn(*args)
            res = []
            for i, m in enumerate(self.members):
                m.dispatch_log.append("epoch")   # ONE shared dispatch
                res.append(m._finish_epoch(first_episode, n_batches,
                                           tree_index(outs, i)))
            return res

    def _epoch_call(self, first_episode: int, n_batches: int,
                    schedule: tuple) -> tuple:
        """(the compiled population epoch, its stacked arguments)."""
        m0 = self.members[0]
        params = m0.cmodel.params
        args = [m._epoch_args(first_episode, n_batches)
                for m in self.members]
        hit = fifo_cached(
            self._pop_epoch_cache, _EPOCH_CACHE_MAX,
            (m0.batch_size, schedule, id(params)),
            lambda h: h[0] is params,
            lambda: (params,
                     jax.jit(self._member_map(
                         m0._make_epoch_fn(schedule),
                         in_axes=(None,) + (0,) * len(args[0])),
                             donate_argnums=(1, 2))))
        return hit[1], (self._params_for_dispatch(params),
                        *self._stack_for_dispatch(args))

    def _run_epoch_chunk(self, first_episode: int,
                         k: int) -> List[List[EpisodeRecord]]:
        K = self.members[0].batch_size
        nb, rem = divmod(k, K)
        chunks = self.run_epoch(first_episode, nb)
        if rem:           # trailing partial batch: the per-batch tails
            tail = self._run_fused_chunk(first_episode + nb * K, rem)
            chunks = [c + t for c, t in zip(chunks, tail)]
        return chunks

    def run(self, episodes: Optional[int] = None,
            verbose: bool = False) -> List[SearchResult]:
        """Run all members for the same episode count; returns one
        ``SearchResult`` per member, aligned with ``self.members``."""
        n = episodes or min(m.cfg.episodes for m in self.members)
        histories = [[] for _ in self.members]
        bests = [None for _ in self.members]
        saved = [m._defer_updates for m in self.members]
        try:
            for m in self.members:
                m._defer_updates = True
            e = 0
            while e < n:
                k = min(self.members[0]._chunk_size(), n - e)
                if self.fuse_rollouts and self._epochs_fusable():
                    chunks = self._run_epoch_chunk(e, k)
                elif self.fuse_rollouts and self._rollouts_fusable() \
                        and k < self.members[0].batch_size:
                    chunks = self._run_fused_chunk(e, k)
                else:
                    # members whose epochs can't share one trace keep
                    # their own per-member epoch decomposition
                    chunks = [m._run_chunk(e, k) for m in self.members]
                for i, recs in enumerate(chunks):
                    for rec in recs:
                        histories[i].append(rec)
                        if bests[i] is None or rec.reward > bests[i].reward:
                            bests[i] = rec
                self._dispatch_updates()
                if verbose:
                    last = e + k - 1
                    row = " ".join(
                        f"{m.cfg.methods}:{histories[i][-1].reward:+.3f}"
                        for i, m in enumerate(self.members))
                    print(f"  ep {last:4d} rewards [{row}]")
                e += k
        finally:
            for m, flag in zip(self.members, saved):
                m._defer_updates = flag
        return [SearchResult(history=histories[i], best=bests[i],
                             ref_latency_s=m.ref_lat.total_s,
                             ref_accuracy=m.ref_acc)
                for i, m in enumerate(self.members)]

    def _dispatch_updates(self):
        """One vmapped chunk for the whole population when the members'
        budgets agree; per-member fused flushes otherwise."""
        ns = [m._pending_updates for m in self.members]
        ready = all(len(m.replay) >= m.agent.cfg.batch_size
                    for m in self.members)
        if len(set(ns)) == 1 and ns[0] > 0 and ready:
            n = ns[0]
            states = self._stack_for_dispatch(
                [m.agent.state_for_dispatch() for m in self.members])
            datas = self._stack_for_dispatch(
                [m.replay.data for m in self.members])
            # states are freshly stacked and never reused after the
            # call, so the megabatched path may donate them in place
            new_states, _losses = population_update_chunk(
                self.members[0].agent.cfg, states, datas, n, donate=True)
            for i, m in enumerate(self.members):
                m.agent.adopt_state(tree_index(new_states, i))
                m._pending_updates = 0
                m.dispatch_log.append("update")   # shared dispatch
        else:
            for m in self.members:
                m._flush_updates()


class FleetSearch(PopulationSearch):
    """Mesh-sharded population search with preemption-safe epoch
    checkpoints — the "search-as-a-service" driver.

    ``PopulationSearch`` already runs the whole population's epoch as ONE
    ``jit(vmap(epoch))`` over stacked per-member carries, but the stack
    lives on one device, so P members time-slice it. ``FleetSearch``
    commits every stacked dispatch operand to a device mesh with
    ``NamedSharding(mesh, P("data"))`` along the member axis
    (``_stack_for_dispatch``) and maps the epoch and rollout over it under
    ``shard_map`` (``_member_map``; the weights are replicated): the SAME
    program then executes one member per device (members beyond the
    ``data`` extent round-robin; the stack is padded up to a multiple of
    it by repeating the last member, whose extra outputs are discarded).
    Per-member math never mixes member rows, so the program contains no
    collectives — and the Pallas kernels inside it, which the compiler
    cannot partition on its own, run per device as they are.

    Preemption safety: every ``ckpt_every`` completed epochs the stacked
    carry — ``AgentState``, ``DeviceReplay`` ring, rollout PRNG key per
    member — is checkpointed through the atomic async writer
    (``checkpoint.checkpointing.save_async``); the manifest records the
    mesh shape, the epoch cursor, per-member seeds/methods, and the ring
    ptr/size mirrors. ``restore_latest_checkpoint`` re-shards the carry
    onto the *current* mesh — including a smaller one after device loss
    (``fault_tolerance.elastic_data_axis`` picks the data extent the
    survivors support) — and the next ``run_fleet`` call resumes from the
    restored cursor. On the same mesh the resume is bit-exact: the carry
    holds every PRNG stream and the update schedule is a pure function of
    (episode cursor, restored ring size). A ``StepMonitor`` times each
    epoch dispatch and flags stragglers (``monitor.summary()``).

    ``mesh=None`` degrades to plain single-device ``PopulationSearch``
    dispatch while keeping the checkpoint/resume machinery — the fleet
    semantics are mesh-size independent by construction.
    """

    def __init__(self, members: Sequence[CompressionSearch], mesh=None,
                 fuse_rollouts: bool = True, ckpt_dir: Optional[str] = None,
                 ckpt_every: int = 1, keep: int = 3,
                 ft_cfg: Optional[FaultToleranceConfig] = None):
        super().__init__(members, fuse_rollouts=fuse_rollouts)
        if not self._epochs_fusable():
            raise ValueError(
                "FleetSearch members must share one epoch trace (same "
                "specs/sensitivity/context/methods/model/reward — vary "
                "seeds or hardware targets instead)")
        if mesh is not None and "data" not in mesh.axis_names:
            raise ValueError(
                f"FleetSearch mesh needs a 'data' axis to shard the "
                f"member dimension; got axes {mesh.axis_names}")
        self.mesh = mesh
        self.monitor = StepMonitor(ft_cfg or FaultToleranceConfig())
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = max(1, int(ckpt_every))
        self._ckpt = AsyncCheckpointer(ckpt_dir, keep=keep) \
            if ckpt_dir else None
        self.epoch_cursor = 0      # episodes completed (per member)
        self.epochs_run = 0        # epoch dispatches completed
        self._replicated = None    # (params, params replicated on mesh)

    # ------------------------------------------------------ mesh placement
    def _stack_for_dispatch(self, trees):
        if self.mesh is None:
            return tree_stack(trees)
        stacked = tree_stack(pad_members(list(trees),
                                         self.mesh.shape["data"]))
        return jax.device_put(stacked,
                              population_shardings(stacked, self.mesh))

    def _member_map(self, fn, in_axes=0):
        """Each device maps ``fn`` over its own slice of the members
        under ``shard_map``: the Pallas kernels in the epoch and the
        rollout cannot be partitioned automatically, and no member's
        math reads another's, so the program needs no collectives."""
        mapped = jax.vmap(fn, in_axes=in_axes)
        if self.mesh is None:
            return mapped
        # check_vma off: carries the epoch builds from constants would
        # need marking as per-device, and there is no collective to check
        return jax.shard_map(
            mapped, mesh=self.mesh, out_specs=PartitionSpec("data"),
            in_specs=jax.tree.map(
                lambda a: PartitionSpec() if a is None
                else PartitionSpec("data"), in_axes,
                is_leaf=lambda a: a is None),
            check_vma=False)

    def _params_for_dispatch(self, params):
        """Replicate the weights over the mesh once per params object, so
        an epoch dispatch does not copy them to every device again."""
        if self.mesh is None:
            return params
        if self._replicated is None or self._replicated[0] is not params:
            self._replicated = (params, jax.device_put(
                params, replicated(self.mesh)))
        return self._replicated[1]

    # ------------------------------------------------------- checkpointing
    def _fleet_carry(self) -> dict:
        """The checkpointable stacked epoch carry. ``state_for_dispatch``
        folds the host-side norm/reward-MA mirrors into the pytree first,
        so the checkpoint is self-contained."""
        return {
            "agent": tree_stack([m.agent.state_for_dispatch()
                                 for m in self.members]),
            "ring": tree_stack([m.replay.data for m in self.members]),
            "rollout_key": jnp.stack([m._rollout_key
                                      for m in self.members]),
        }

    def _manifest_extra(self) -> dict:
        return {
            "epoch_cursor": int(self.epoch_cursor),
            "epochs_run": int(self.epochs_run),
            "mesh_shape": dict(self.mesh.shape)
            if self.mesh is not None else None,
            "member_seeds": [int(m.cfg.seed) for m in self.members],
            "member_methods": [m.cfg.methods for m in self.members],
            "ring_ptr": [int(m.replay.ptr) for m in self.members],
            "ring_size": [int(m.replay.size) for m in self.members],
            "monitor": self.monitor.summary(),
        }

    def save_checkpoint(self, wait: bool = False):
        """Atomic async save of the stacked carry (one step per completed
        epoch). The snapshot happens now; the write runs in the
        background and the previous checkpoint stays intact until the new
        LATEST pointer lands."""
        if self._ckpt is None:
            raise ValueError("FleetSearch was built without ckpt_dir")
        save_async(self._ckpt, self.epochs_run, self._fleet_carry(),
                   self._manifest_extra())
        if wait:
            self._ckpt.wait()

    def restore_latest_checkpoint(self, directory: Optional[str] = None):
        """Restore the newest intact checkpoint and re-shard the carry
        onto the CURRENT mesh (which may be smaller than the one that
        saved it — elastic resume). Returns the manifest extra, or None
        when no checkpoint exists. On the same mesh shape the subsequent
        ``run_fleet`` continuation is bit-exact."""
        directory = directory or self.ckpt_dir
        if directory is None:
            raise ValueError("no checkpoint directory given")
        like = self._fleet_carry()
        shardings = None
        if self.mesh is not None and \
                len(self.members) % self.mesh.shape["data"] == 0:
            # direct re-shard; a non-dividing member count is placed by
            # the next _stack_for_dispatch (which pads) instead
            shardings = population_shardings(like, self.mesh)
        tree, step, extra = restore_latest(directory, like, shardings)
        if tree is None:
            return None
        P = len(self.members)
        if len(extra.get("member_seeds", [])) != P:
            raise ValueError(
                f"checkpoint holds {len(extra.get('member_seeds', []))} "
                f"members, fleet has {P}")
        for i, m in enumerate(self.members):
            st = tree_index(tree["agent"], i)
            m.agent.adopt_state(st)
            norm = jax.device_get((st.norm_count, st.norm_mean,
                                   st.norm_var))
            m.agent.norm.count = float(norm[0])
            m.agent.norm.mean = np.asarray(norm[1], np.float32)
            m.agent.norm.var = np.asarray(norm[2], np.float32)
            m.replay.load(tree_index(tree["ring"], i),
                          extra["ring_ptr"][i], extra["ring_size"][i])
            m._rollout_key = tree["rollout_key"][i]
        self.epoch_cursor = int(extra["epoch_cursor"])
        self.epochs_run = int(extra["epochs_run"])
        return extra

    # --------------------------------------------------------- fleet loop
    def run_fleet(self, episodes: int,
                  verbose: bool = False) -> List[SearchResult]:
        """Run whole fleet epochs from ``self.epoch_cursor`` (0, or the
        restored checkpoint's cursor) until ``episodes`` total episodes
        per member, checkpointing every ``ckpt_every`` epochs. Histories
        cover only the episodes run by THIS call — a resumed fleet
        returns the post-restore tail, which is what resume parity tests
        compare."""
        K = self.members[0].batch_size
        E = self.members[0].epoch_batches
        if episodes % K:
            raise ValueError(
                f"episodes ({episodes}) must be a multiple of the "
                f"episode batch size ({K}) — fleets run whole batches")
        histories = [[] for _ in self.members]
        bests: List[Optional[EpisodeRecord]] = [None] * len(self.members)
        while self.epoch_cursor < episodes:
            nb = min(E, (episodes - self.epoch_cursor) // K)
            t0 = time.perf_counter()
            # run_epoch ends with the epoch's single blocking host
            # readback, so this wall time covers the full dispatch
            chunks = self.run_epoch(self.epoch_cursor, nb)
            self.epochs_run += 1
            self.monitor.record(self.epochs_run,
                                time.perf_counter() - t0)
            self.epoch_cursor += nb * K
            for i, recs in enumerate(chunks):
                for rec in recs:
                    histories[i].append(rec)
                    if bests[i] is None or rec.reward > bests[i].reward:
                        bests[i] = rec
            if self._ckpt is not None and \
                    self.epochs_run % self.ckpt_every == 0:
                self.save_checkpoint()
            if verbose:
                row = " ".join(
                    f"{m.cfg.methods}:{histories[i][-1].reward:+.3f}"
                    for i, m in enumerate(self.members))
                print(f"  epoch {self.epochs_run:4d} "
                      f"ep {self.epoch_cursor:5d} rewards [{row}]")
        if self._ckpt is not None:
            self._ckpt.wait()
        return [SearchResult(history=histories[i], best=bests[i],
                             ref_latency_s=m.ref_lat.total_s,
                             ref_accuracy=m.ref_acc)
                for i, m in enumerate(self.members)]
