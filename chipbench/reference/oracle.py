"""Plain reference of the search's analytic latency oracle, its reward
and its legality rules, in float64 numpy.

The oracle is the repository's documented TPU v5e roofline (ROADMAP,
core/latency.py docstrings): per unit, time = max(compute, memory) with
compute = 2 * rows * pad(k) * pad(n) * matrices / peak (the int8 peak
when weights and activations are both 8 bits or fewer, else the bf16
peak) and memory = (weight bytes + activation bytes) / HBM bandwidth.
``pad`` rounds up to the MXU lane width; pruning shrinks the unit's
output dim and the input dim of the unit that consumes it; weights take
2 bytes at 9+ bits, 1 at 5-8, 0.5 below; activations 1 byte at 8 bits
or fewer, else 2 (outputs 2). An embedding is a gather of one row per
token. Each attention layer adds a term for its scores and its KV-cache
reads, and every unit and term one dispatch overhead.

The units (one dict per compressible unit: its kind, its input and
output dims, weight count, prunable dim and granule, the unit whose
pruning shrinks its input, and whether int4 packing is possible) come
from the configuration's family module.

Reward (paper's absolute reward): ``acc + beta * |lat / (c * lat_ref) - 1|``.
"""
from __future__ import annotations

import math

import numpy as np


def _pad(x, align):
    return math.ceil(max(x, 1.0) / align) * align


def latency(us: list, keep, wb, ab, hw: dict, ctx: dict,
            dtype=None) -> float:
    """Oracle seconds of one policy (arrays in ``units`` order). With a
    control ``dtype`` ("bf16", "int8" meaning bf16 here, or a numpy
    type) every term and partial sum is rounded to it."""
    rnd = _rounder(dtype)
    T, align, bw = float(ctx["tokens"]), hw["mxu_align"], hw["hbm_bw"]
    by_name = {u["name"]: j for j, u in enumerate(us)}
    frac = [keep[j] / u["prune"] if u["prune"] else 1.0
            for j, u in enumerate(us)]
    total, n_ops = 0.0, 0
    for j, u in enumerate(us):
        w_b, a_b = wb[j], ab[j]
        wpe = 2.0 if w_b >= 9 else (1.0 if w_b >= 5 else 0.5)
        ape = 1.0 if a_b <= 8 else 2.0
        peak = hw["peak_int8"] if (w_b <= 8 and a_b <= 8) else hw["peak_bf16"]
        fin = frac[by_name[u["owner"]]] if u.get("owner") else 1.0
        fout = frac[j]
        n_ops += 1
        if u["kind"] == "embed":
            total = rnd(total + rnd(T * u["o"] * wpe / bw))
            continue
        rows, k = T, u["i"] * fin
        mats = max(1.0, u["w"] / (u["i"] * u["o"]))
        if u["kind"] == "qkv":
            fixed = 2 * u["kv"] * u["hd"]
            n = fout * (u["o"] - fixed) + fixed
        else:
            n = u["o"] * fout
        flops = 2.0 * rows * _pad(k, align) * _pad(n, align) * mats
        mem = u["w"] * fout * fin * wpe + rows * k * ape + rows * n * 2.0
        total = rnd(total + rnd(max(flops / peak, mem / bw)))
        if u["kind"] == "qkv" and ctx["seq_ctx"] > 0:
            S = float(ctx["seq_ctx"])
            ef = 4.0 * T * S * u["hd"] * keep[j]
            if ctx["mode"] in ("train", "prefill"):
                ef *= 0.5
            cache = T * S * 2 * u["kv"] * u["hd"] * 2.0
            total = rnd(total + rnd(max(ef / hw["peak_bf16"], cache / bw)))
            n_ops += 1
    return float(rnd(total + rnd(n_ops * hw["op_overhead"])))


def _rounder(dtype):
    if dtype is None:
        return lambda x: x
    import ml_dtypes
    dt = ml_dtypes.bfloat16 if dtype in ("bf16", "int8") else dtype
    return lambda x: float(np.asarray(x, np.float64).astype(dt))


def reference_policy(us: list):
    keep = np.asarray([u["prune"] for u in us], np.float64)
    full = np.full(len(us), 32.0)
    return keep, full, full.copy()


def reward(acc, lat, ref_lat, rcfg: dict):
    return acc + rcfg["beta"] * abs(lat / (rcfg["target_ratio"] * ref_lat)
                                    - 1.0)


def illegal_units(us: list, keep, wb, ab) -> int:
    """How many units of one policy break the legality rules: kept
    counts a multiple of the unit's granule (at least one granule, at
    most the prunable dim, or the whole dim), unprunable units unpruned,
    widths (32, 32), (8, 8) or a MIX pair in 1..6 where int4 packing is
    possible (a contracted dim that is a multiple of 256)."""
    bad = 0
    for j, u in enumerate(us):
        k, w, a = keep[j], wb[j], ab[j]
        if u["prune"]:
            ok_keep = k == u["prune"] or (
                u["gran"] <= k <= u["prune"] and k % u["gran"] == 0)
        else:
            ok_keep = k == 0
        mix = 1 <= w <= 6 and 1 <= a <= 6 and u["mix"] and u["i"] % 256 == 0
        ok_bits = (w, a) in ((32, 32), (8, 8)) or mix
        bad += int(not (ok_keep and ok_bits))
    return bad
