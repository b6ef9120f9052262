"""Search-loop mechanics (short runs; learning quality is benchmarked, not
unit-tested)."""
import numpy as np
import pytest

from repro.core.ddpg import DDPGConfig
from repro.core.latency import LatencyContext
from repro.core.reward import RewardConfig
from repro.core.search import CompressionSearch, SearchConfig
from repro.core.state import state_dim


def _search(tiny_lm, methods, episodes=4, batch_size=8, epoch_batches=1):
    cm, batch = tiny_lm
    ctx = LatencyContext(tokens=1, seq_ctx=256, mode="decode", batch=1)
    scfg = SearchConfig(
        methods=methods, episodes=episodes,
        reward=RewardConfig(target_ratio=0.5),
        ddpg=DDPGConfig(warmup_episodes=2, updates_per_episode=2,
                        batch_size=16, buffer_size=256))
    return CompressionSearch(cm, batch, scfg, ctx, batch_size=batch_size,
                             epoch_batches=epoch_batches)


def _assert_legal(specs, policy):
    for s, c in zip(specs, policy.cmps):
        if s.prunable and s.prune_dim:
            assert c.keep % s.prune_granularity == 0 or c.keep == s.prune_dim
        if c.mode == "MIX":
            assert s.mix_supported
        if not s.quantizable:
            assert c.mode == "FP32"


# K=2 episodes per batch, E=2 batches per epoch: 8 episodes end on a
# whole epoch, 6 inside an epoch (its last has one batch), 7 inside a
# batch (the per-batch tail runs the last episode)
TAILS = {"": 8, "-epoch-tail": 6, "-batch-tail": 7}


@pytest.mark.parametrize("methods,episodes", [
    pytest.param(m, n, id=m + tail)
    for m in ("p", "q", "pq") for tail, n in TAILS.items()])
def test_search_runs_all_agents(tiny_lm, methods, episodes):
    """End-to-end engine smoke for every agent and every tail of run():
    episode numbering, legality, replay fill, finite records — with
    live update dispatches."""
    search = _search(tiny_lm, methods, episodes=episodes, batch_size=2,
                     epoch_batches=2)
    res = search.run()
    assert [r.episode for r in res.history] == list(range(episodes))
    for rec in res.history:
        assert np.isfinite(rec.reward)
        assert 0.0 <= rec.accuracy <= 1.0
        assert rec.latency_s > 0
        assert len(rec.policy.cmps) == len(search.specs)
        _assert_legal(search.specs, rec.policy)
    # shared-episode-reward transitions, all pushed
    assert len(search.replay) == min(256, episodes * len(search.steps))
    # two epoch programs in each case; the per-batch tail only for 7
    assert search.dispatch_log.count("epoch") == 2
    assert ("rollout" in search.dispatch_log) == (episodes % 2 == 1)


def test_policy_cmps_legal(tiny_lm):
    search = _search(tiny_lm, "pq")
    for rec in search.run_epoch(0, 1):
        _assert_legal(search.specs, rec.policy)


def test_reference_ratio_one(tiny_lm):
    search = _search(tiny_lm, "pq")
    from repro.core.latency import policy_latency
    lat = policy_latency(search.specs, search.ref_policy, search.hw,
                         search.ctx)
    assert lat.total_s == pytest.approx(search.ref_lat.total_s)


def test_transitions_pushed(tiny_lm):
    search = _search(tiny_lm, "pq", batch_size=1)
    search.run_epoch(0, 1)
    assert len(search.replay) == len(search.steps)


def test_state_dim_matches(tiny_lm):
    search = _search(tiny_lm, "pq")
    assert search.agent.cfg.state_dim == state_dim(3)


def test_pruning_agent_skips_dependent_layers(tiny_lm):
    search = _search(tiny_lm, "p")
    names = [search.specs[i].name for i in search.steps]
    assert all("down" not in n and "attn_out" not in n for n in names)
    assert not any(n in ("embed", "head") for n in names)
