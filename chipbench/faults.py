"""Faults planted in the program under test, to show that ``correct``
catches them: each ``plant`` patches one function of the program in
this process and returns the function that removes the patch.

* ``state_unchanged``: every DDPG update step returns its state as it
  was given;
* ``half_batch``: validation scores half of the validation batch, and
  each update step samples half a minibatch, each taking its mean over
  what is left;
* ``answer_altered``: every episode's reward is moved by 0.1 where the
  epoch computes it.
"""
from __future__ import annotations

FAULTS = ("state_unchanged", "half_batch", "answer_altered")


def _patch(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    return lambda: setattr(obj, name, old)


def plant(fault: str):
    from repro.core import compress, search
    if fault == "state_unchanged":
        return _patch(search, "update_step",
                      lambda cfg, st, batch: (st, (0.0, 0.0)))
    if fault == "half_batch":
        acc = compress.CompressibleLM.accuracy

        def half(self, batch, cspec=None, params=None):
            n = next(iter(batch.values())).shape[0] // 2
            return acc(self, {k: v[:n] for k, v in batch.items()}, cspec,
                       params)
        undo = [_patch(compress.CompressibleLM, "accuracy", half)]
        sample = search.device_replay_sample
        undo.append(_patch(search, "device_replay_sample",
                           lambda data, key, batch: sample(data, key,
                                                           batch // 2)))
        return lambda: [u() for u in undo]
    if fault == "answer_altered":
        reward = search.compute_reward_batch
        return _patch(search, "compute_reward_batch",
                      lambda *a, **k: reward(*a, **k) + 0.1)
    raise ValueError(f"unknown fault {fault!r}")
