"""The host-stepped episode rollout: the reference the search engine's
traced rollout (``make_rollout_fn``) is compared against.

It walks the actionable units one host step at a time with the numpy
oracle (``policy_latency_batch``), the host state builder
(``build_state_batch``) and the scalar action mapping (``map_actions``),
then validates the finished policies and computes their rewards in
numpy. The actor is a callable, so a test can replay the engine's exact
exploration draws.
"""
import copy

import numpy as np

from repro.core.latency import policy_latency_batch
from repro.core.policy import map_actions, stack_policies
from repro.core.reward import compute_reward_batch
from repro.core.spec import effective_bits
from repro.core.state import build_state_batch


def host_rollout(search, first_episode: int, k: int, act):
    """Roll ``k`` episodes from ``first_episode`` on ``search``'s model,
    oracle and schedule without touching its agent, ring or PRNG.

    ``act(S, sigmas, warmup) -> A`` maps the (k, S) states of one unit
    step to (k, A) actions. Returns ``(states, actions, policies, accs,
    lats, rewards, sigmas)`` with ``states``/``actions`` stacked
    (T, k, ·) in step order.
    """
    cfg = search.cfg
    warmup, sigmas = search._batch_schedule(first_episode, k)
    partials = [copy.deepcopy(search.ref_policy) for _ in range(k)]
    # (k, L) policy arrays, updated in place as units are decided
    pb = stack_policies(search.specs, partials)
    prev_a = np.zeros((k, search.agent.cfg.action_dim), np.float32)
    step_states, step_actions = [], []
    for t in search.steps:
        cur = policy_latency_batch(search.specs, pb, search.hw, search.ctx,
                                   cfg.window, calib=search.calib)
        S = build_state_batch(search.specs, t, cur, search.sens, prev_a,
                              search.ref_lat)
        A = act(S, sigmas, warmup)
        for j in range(k):
            cmp = map_actions(search.specs[t], A[j], cfg.methods)
            # single-method agents keep the other method's parameters
            prev = partials[j].cmps[t]
            if cfg.methods == "q":
                cmp.keep = prev.keep
            elif cfg.methods == "p":
                cmp.mode, cmp.w_bits, cmp.a_bits = (prev.mode, prev.w_bits,
                                                    prev.a_bits)
            partials[j].cmps[t] = cmp
            pb.keep[j, t] = cmp.keep
            pb.w_bits[j, t], pb.a_bits[j, t] = effective_bits(cmp)
        step_states.append(S)
        step_actions.append(A)
        prev_a = A
    accs = np.asarray(search.cmodel.accuracy_policy_batch(search.val_batch,
                                                          pb))
    lats = policy_latency_batch(search.specs, pb, search.hw, search.ctx,
                                cfg.window, calib=search.calib).total_s
    rewards = compute_reward_batch(cfg.reward, accs, lats,
                                   search.ref_lat.total_s, xp=np)
    return (np.stack(step_states), np.stack(step_actions), partials, accs,
            lats, rewards, sigmas)
