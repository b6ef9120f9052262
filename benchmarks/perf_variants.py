"""§Perf hillclimbing driver: lower chosen (arch × shape) cells under
optimization variants and report the roofline-term deltas.

Run in a fresh process (512 host devices):
  PYTHONPATH=src:. python benchmarks/perf_variants.py --cell qwen2_decode
Outputs artifacts/perf_<cell>.json with one row per variant.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import json
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import repro.launch.dryrun as DR
from repro.launch.mesh import auto_mesh, make_production_mesh
from repro.models.registry import get_config


def lower_variant(arch, shape_name, mesh, *, deploy_bits=None, cache_bits=16,
                  overrides=None, label=""):
    cfg = get_config(arch)
    from repro.configs.base import SHAPES_BY_NAME
    shape = SHAPES_BY_NAME[shape_name]
    if shape.mode == "train":
        cfg = cfg.replace(remat="full")
    if overrides:
        cfg = cfg.replace(**overrides)
    scanned = cfg.scan_layers and cfg.homogeneous
    if scanned:
        # probe extrapolation (see dryrun): 1- and 2-layer unrolled compiles
        from repro.launch.inputs import model_flops
        r1, _ = DR._lower(cfg.replace(num_layers=1, scan_layers=False),
                          shape, mesh, deploy_bits=deploy_bits,
                          cache_bits=cache_bits)
        r2, _ = DR._lower(cfg.replace(num_layers=2, scan_layers=False),
                          shape, mesh, deploy_bits=deploy_bits,
                          cache_bits=cache_bits)
        row = DR._recombine(r1, r1, r2, cfg.num_layers, DR.V5E,
                            model_flops(cfg, shape), r1["chips"])
    else:
        row, _ = DR._lower(cfg, shape, mesh, deploy_bits=deploy_bits,
                           cache_bits=cache_bits)
    row["variant"] = label
    row["arch"], row["shape"] = arch, shape_name
    keep = ("variant", "arch", "shape", "chips", "flops", "bytes",
            "collective_bytes", "compute_s", "memory_s", "collective_s",
            "dominant", "step_s", "model_flops", "useful_flops_ratio",
            "roofline_fraction", "per_collective")
    return {k: row[k] for k in keep if k in row}


CELLS = {
    # Cell C (paper-representative): weight-memory-bound single-stream-ish
    # decode; the Galen policy attacks exactly this term.
    "qwen2_decode": [
        ("baseline_bf16", dict()),
        ("paper_int8_weights", dict(deploy_bits=8)),
        ("int4_weights", dict(deploy_bits=4)),
        ("int4_weights+int8_cache", dict(deploy_bits=4, cache_bits=8)),
        ("int4+cache8+pruned25", dict(deploy_bits=4, cache_bits=8,
                                      overrides={"d_ff": 3712})),
    ],
    # Cell B (worst roofline fraction): MHA (kv=36) long-context decode —
    # cache is length-sharded (36 heads don't divide the model axis).
    "minicpm_decode": [
        ("baseline_bf16", dict()),
        ("paper_int8_weights", dict(deploy_bits=8)),
        ("int8_weights+int8_cache", dict(deploy_bits=8, cache_bits=8)),
        ("int4_weights+int8_cache", dict(deploy_bits=4, cache_bits=8)),
        # B3: reshape the serving mesh so kv=36 divides the model axis ->
        # head-sharded cache, local DUS writes (36 % 4 == 0)
        ("B3_mesh64x4+int8_cache", dict(deploy_bits=8, cache_bits=8,
                                        mesh=(64, 4))),
    ],
    "granite_decode": [
        ("baseline_bf16", dict()),
        ("paper_int8_weights", dict(deploy_bits=8)),
        ("int8_weights+int8_cache", dict(deploy_bits=8, cache_bits=8)),
        ("int4_weights+int8_cache", dict(deploy_bits=4, cache_bits=8)),
    ],
    # Cell A (most collective-bound): MoE training.
    "mixtral_train": [
        ("baseline_cf1.25", dict()),
        ("capacity_factor_1.0", dict(overrides={
            "moe": None})),  # placeholder — replaced below
    ],
}

CELL_TARGETS = {
    "qwen2_decode": ("qwen2-0.5b", "decode_32k"),
    "minicpm_decode": ("minicpm-2b", "decode_32k"),
    "granite_decode": ("granite-3-8b", "decode_32k"),
    "mixtral_train": ("mixtral-8x22b", "train_4k"),
}


def mixtral_variants():
    # NOTE: "baseline" in EXPERIMENTS.md §Perf is the recorded sweep row
    # (pre-A1 sharding rules). Every lowering below includes A1 (vocab-TP
    # embed/unembed — a global rule fix).
    from repro.configs.base import MoEConfig
    rs = MoEConfig(num_experts=8, top_k=2, capacity_factor=1.25,
                   combine="reduce_scatter")
    cf1 = MoEConfig(num_experts=8, top_k=2, capacity_factor=1.0)
    return [
        ("A1_vocab_tp+sharded_ce", dict()),
        ("A2_rs_combine(refuted)", dict(overrides={"moe": rs})),
        ("A1+A3_cf1.0", dict(overrides={"moe": cf1})),
        ("A1+A3+A5_dots_saveable", dict(overrides={
            "moe": cf1, "remat": "dots_saveable"})),
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, choices=list(CELLS))
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    arch, shape = CELL_TARGETS[args.cell]
    variants = mixtral_variants() if args.cell == "mixtral_train" \
        else CELLS[args.cell]
    rows = []
    for label, kw in variants:
        print(f"=== {args.cell}: {label} ===", flush=True)
        kw = dict(kw)
        mesh_v = mesh
        if "mesh" in kw:   # serving-topology variant (e.g. B3)
            shp = kw.pop("mesh")
            mesh_v = auto_mesh(shp, ("data", "model"))
        try:
            row = lower_variant(arch, shape, mesh_v, label=label, **kw)
        except Exception as e:
            import traceback
            traceback.print_exc()
            row = {"variant": label, "error": str(e)}
        rows.append(row)
        print({k: row.get(k) for k in ("variant", "dominant", "step_s",
                                       "compute_s", "memory_s",
                                       "collective_s")}, flush=True)
    out = f"artifacts/perf_{args.cell}.json"
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    print("wrote", out)


if __name__ == "__main__":
    main()
