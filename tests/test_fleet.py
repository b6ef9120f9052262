"""FleetSearch: mesh-sharded population epochs + preemption-safe resume.

Three tiers:
* in-process 1-device tests (mesh construction errors, fleet invariants);
* in-process mesh tests gated by ``conftest.require_devices`` — skipped
  in the ordinary suite, exercised by CI's dedicated multi-device step
  (a fresh pytest process under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``);
* subprocess tests that run the full acceptance scenario on an 8-device
  forced-host CPU mesh: sharded-vs-single-device records parity <=1e-5,
  the shared-dispatch probe, kill-at-epoch-N -> restore -> bit-for-bit
  resume, and the 4->2-device elastic restore.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from conftest import require_devices

TESTS = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(TESTS, "..", "src")
ROOT = os.path.join(TESTS, "..")


# ---------------------------------------------------------------------------
# 1-device tests
# ---------------------------------------------------------------------------

def test_make_dev_mesh_clear_error():
    from repro.launch.mesh import make_dev_mesh
    have = len(jax.devices())
    with pytest.raises(ValueError) as e:
        make_dev_mesh(data=have + 1, model=2)
    msg = str(e.value)
    assert str(2 * (have + 1)) in msg          # names the required count
    assert "xla_force_host_platform_device_count" in msg


def test_require_devices_helper_skips():
    with pytest.raises(pytest.skip.Exception) as e:
        require_devices(len(jax.devices()) + 1)
    assert "xla_force_host_platform_device_count" in str(e.value)


def _fleet_members(tiny_lm, n=2, epoch_batches=2):
    from repro.core.ddpg import DDPGConfig
    from repro.core.latency import LatencyContext
    from repro.core.reward import RewardConfig
    from repro.core.search import CompressionSearch, SearchConfig
    cm, batch = tiny_lm
    ctx = LatencyContext(tokens=1, seq_ctx=256, mode="decode", batch=1)
    members, sens = [], None
    for p in range(n):
        scfg = SearchConfig(
            methods="pq", episodes=32,
            reward=RewardConfig(target_ratio=0.5),
            ddpg=DDPGConfig(warmup_episodes=2, updates_per_episode=2,
                            batch_size=16, buffer_size=256),
            seed=p)
        m = CompressionSearch(cm, batch, scfg, ctx, sens=sens,
                              batch_size=4, epoch_batches=epoch_batches)
        sens = m.sens
        members.append(m)
    return members


def test_fleet_rejects_non_epoch_members(tiny_lm):
    """A member cannot leave epoch mode: the engine rejects
    ``epoch_batches`` < 1."""
    with pytest.raises(ValueError, match="epoch_batches"):
        _fleet_members(tiny_lm, n=2, epoch_batches=0)


def test_fleet_rejects_mesh_without_data_axis(tiny_lm):
    from repro.core.search import FleetSearch
    mesh = jax.make_mesh((1,), ("model",))
    members = _fleet_members(tiny_lm, n=2)
    with pytest.raises(ValueError, match="data"):
        FleetSearch(members, mesh=mesh)


def test_fleet_checkpoint_requires_dir(tiny_lm):
    from repro.core.search import FleetSearch
    fleet = FleetSearch(_fleet_members(tiny_lm, n=2))
    with pytest.raises(ValueError, match="ckpt_dir"):
        fleet.save_checkpoint()
    with pytest.raises(ValueError, match="directory"):
        fleet.restore_latest_checkpoint()


def test_fleet_episodes_must_be_whole_batches(tiny_lm):
    from repro.core.search import FleetSearch
    fleet = FleetSearch(_fleet_members(tiny_lm, n=2))
    with pytest.raises(ValueError, match="multiple"):
        fleet.run_fleet(6)          # batch size is 4


# ---------------------------------------------------------------------------
# mesh-gated in-process tests (run in CI's multi-device step)
# ---------------------------------------------------------------------------

def test_population_shardings_member_axis():
    require_devices(4)
    import jax.numpy as jnp
    import numpy as np
    from repro.core.ddpg import tree_stack
    from repro.distributed.sharding import (member_sharding, pad_members,
                                            population_shardings)
    from repro.launch.mesh import make_dev_mesh
    mesh = make_dev_mesh(data=4, model=1)
    trees = [{"w": jnp.full((3, 2), i, jnp.float32),
              "s": jnp.float32(i)} for i in range(3)]
    padded = pad_members(trees, mesh.shape["data"])
    assert len(padded) == 4 and padded[-1] is trees[-1]
    stacked = tree_stack(padded,
                         shardings=None)
    sh = population_shardings(stacked, mesh)
    placed = jax.device_put(stacked, sh)
    # member axis really spans the data axis, one member per device
    assert len(placed["w"].sharding.device_set) == 4
    assert placed["w"].sharding.spec[0] == "data"
    assert placed["s"].shape == (4,)
    assert len(placed["s"].sharding.device_set) == 4
    np.testing.assert_array_equal(np.asarray(placed["w"]),
                                  np.asarray(stacked["w"]))
    # 0-d leaves replicate (no member axis to split)
    assert member_sharding(mesh, 0).spec == jax.sharding.PartitionSpec()


def test_tree_stack_places_on_mesh():
    require_devices(2)
    import jax.numpy as jnp
    from repro.core.ddpg import tree_stack
    from repro.distributed.sharding import population_shardings
    from repro.launch.mesh import make_dev_mesh
    mesh = make_dev_mesh(data=2, model=1)
    trees = [{"w": jnp.ones((4, 4)) * i} for i in range(2)]
    stacked = tree_stack(trees)
    placed = tree_stack(trees,
                        shardings=population_shardings(stacked, mesh))
    assert len(placed["w"].sharding.device_set) == 2


# ---------------------------------------------------------------------------
# subprocess acceptance tests (8 forced host devices)
# ---------------------------------------------------------------------------

_PARITY_RESUME = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import json
    import tempfile
    import jax
    from _dispatch_probes import assert_population_epoch_dispatch_count
    from repro.launch.fleet import tiny_fleet

    d = tempfile.mkdtemp()
    out = {"devices": len(jax.devices())}

    def recs(results):
        return [[(r.episode, r.reward, r.accuracy, r.latency_s)
                 for r in res.history] for res in results]

    # sharded P=4 fleet on a 4-device mesh, checkpointing every epoch
    fa = tiny_fleet(members=4, data=4, seed0=0, ckpt_dir=d, ckpt_every=1)
    head = recs(fa.run_fleet(16))        # epochs 1-2 (checkpointed)
    fa._ckpt.wait()
    fa._ckpt = None                      # LATEST stays at epoch 2
    tail = recs(fa.run_fleet(24))        # epoch 3 (post-"kill" reference)
    out["mesh"] = dict(fa.mesh.shape)

    # dispatch probe: a steady-state epoch is ONE shared sharded dispatch
    probe = assert_population_epoch_dispatch_count(fa, fa.epoch_cursor, 2)
    out["pop_epoch"] = probe["pop_epoch"]

    # parity: the same fleet pinned to one device (no mesh)
    fs = tiny_fleet(members=4, data=0, seed0=0)
    solo = recs(fs.run_fleet(24))
    md = 0.0
    for ml, sl in zip([h + t for h, t in zip(head, tail)], solo):
        assert len(ml) == len(sl)
        for p, q in zip(ml, sl):
            assert p[0] == q[0]
            md = max(md, abs(p[1] - q[1]), abs(p[2] - q[2]),
                     abs(p[3] - q[3]) / max(1e-30, abs(q[3])))
    out["parity_maxdiff"] = md

    # kill-at-epoch-2 -> restore_latest -> resume, bit-for-bit
    fr = tiny_fleet(members=4, data=4, seed0=0, ckpt_dir=d)
    extra = fr.restore_latest_checkpoint()
    out["resume_cursor"] = extra["epoch_cursor"]
    out["manifest_mesh"] = extra["mesh_shape"]
    out["manifest_seeds"] = extra["member_seeds"]
    out["manifest_ring_size"] = extra["ring_size"]
    out["resume_bit_exact"] = recs(fr.run_fleet(24)) == tail
    print(json.dumps(out))
""")

_ELASTIC_RESUME = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import json
    import tempfile
    import jax
    from repro.distributed.fault_tolerance import elastic_data_axis
    from repro.launch.fleet import tiny_fleet
    from repro.launch.mesh import make_dev_mesh

    d = tempfile.mkdtemp()

    def recs(results):
        return [[(r.episode, r.reward, r.accuracy, r.latency_s)
                 for r in res.history] for res in results]

    # save at epoch 2 on a 4-device mesh, keep running uninterrupted
    fa = tiny_fleet(members=4, data=4, seed0=0, ckpt_dir=d, ckpt_every=1)
    fa.run_fleet(16)
    fa._ckpt.wait()
    fa._ckpt = None
    ref = recs(fa.run_fleet(24))         # epoch 3, uninterrupted

    # restart after losing half the devices: elastic_data_axis picks the
    # data extent 2 survivors support; restore re-shards onto that mesh
    data = elastic_data_axis(1, 2, 1)
    fb = tiny_fleet(members=4, seed0=0, ckpt_dir=d,
                    mesh=make_dev_mesh(data, 1))
    extra = fb.restore_latest_checkpoint()
    got = recs(fb.run_fleet(24))
    md = 0.0
    for ml, sl in zip(ref, got):
        assert len(ml) == len(sl)
        for p, q in zip(ml, sl):
            assert p[0] == q[0]
            md = max(md, abs(p[1] - q[1]), abs(p[2] - q[2]),
                     abs(p[3] - q[3]) / max(1e-30, abs(q[3])))
    print(json.dumps({"elastic_data": data, "maxdiff": md,
                      "resume_cursor": extra["epoch_cursor"],
                      "saved_mesh": extra["mesh_shape"]}))
""")


def _run_subprocess(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        os.path.abspath(d) for d in (SRC, ROOT, TESTS))
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_fleet_subprocess_parity_probe_resume():
    """ISSUE 8 acceptance: on an 8-device forced-host CPU mesh a P=4
    population epoch runs as sharded dispatches with records parity
    <=1e-5 vs the single-device path, the dispatch-count probe holds,
    and kill-at-epoch-N -> restore_latest -> resume reproduces the
    uninterrupted run's records bit-for-bit."""
    out = _run_subprocess(_PARITY_RESUME)
    assert out["devices"] == 8
    assert out["mesh"] == {"data": 4, "model": 1}
    assert out["pop_epoch"] == 1
    assert out["parity_maxdiff"] <= 1e-5, out
    assert out["resume_cursor"] == 16
    assert out["manifest_mesh"] == {"data": 4, "model": 1}
    assert out["manifest_seeds"] == [0, 1, 2, 3]
    assert all(s > 0 for s in out["manifest_ring_size"])
    assert out["resume_bit_exact"] is True, out


@pytest.mark.slow
def test_fleet_subprocess_elastic_resume():
    """Satellite: save at epoch N on a 4-device mesh, restore onto 2
    devices via ``elastic_data_axis``, epoch N+1 records parity <=1e-5
    vs the uninterrupted run."""
    out = _run_subprocess(_ELASTIC_RESUME)
    assert out["elastic_data"] == 2
    assert out["saved_mesh"] == {"data": 4, "model": 1}
    assert out["resume_cursor"] == 16
    assert out["maxdiff"] <= 1e-5, out
