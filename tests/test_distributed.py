"""Distribution tests: sharding rules + a subprocess multi-device dry-run
(the main process must keep seeing exactly one CPU device)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_main_process_single_device():
    assert len(jax.devices()) == 1


def test_param_rules_cover_model_paths():
    from repro.distributed.sharding import logical_axes_for_path
    cases = {
        "blocks/attn/wq/w": ("fsdp", "heads"),
        "blocks/mlp/w_up/w": ("fsdp", "ff"),
        "blocks/moe/w_down": ("experts", "ff", "fsdp"),
        "embed": ("vocab", None),
        "blocks/ssm/in_proj": ("fsdp", "heads"),
        "blocks/rglru/w_x": ("fsdp", "ff"),
        "final_norm/scale": (None,),
    }
    for path, want in cases.items():
        nd = len(want)
        got = logical_axes_for_path(path, nd, stacked=False)
        assert got == want, (path, got, want)


def test_stacked_prepends_layers():
    from repro.distributed.sharding import logical_axes_for_path
    got = logical_axes_for_path("blocks/mlp/w_up/w", 3, stacked=True)
    assert got == ("layers", "fsdp", "ff")


SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import repro.launch.dryrun as DR
    import repro.models.registry as REG
    import repro.configs.base as CB
    from repro.launch.mesh import make_dev_mesh

    mesh = make_dev_mesh(data=2, model=4)
    orig = REG.get_config
    DR.get_config = lambda a, smoke=False: orig(a, smoke=True).replace(
        scan_layers=orig(a).scan_layers)
    CB.SHAPES_BY_NAME["train_4k"] = CB.ShapeConfig("train_4k", 64, 8, "train")
    CB.SHAPES_BY_NAME["decode_32k"] = CB.ShapeConfig(
        "decode_32k", 128, 8, "decode")
    DR.SHAPES_BY_NAME = CB.SHAPES_BY_NAME
    out = {}
    for arch, shp in [("qwen2-0.5b", "train_4k"), ("mixtral-8x22b", "train_4k"),
                      ("qwen2-0.5b", "decode_32k")]:
        row, _ = DR.lower_cell(arch, shp, mesh, probes=False)
        out[f"{arch}:{shp}"] = {k: row[k] for k in
                                ("flops", "collective_bytes", "dominant")}
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_dryrun_subprocess_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", SUBPROC], env=env,
                         capture_output=True, text=True, timeout=500)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    for key, row in out.items():
        assert row["flops"] > 0, key
        assert row["collective_bytes"] > 0, key  # SPMD really sharded
