"""What the search driver needs of a hybrid Mamba-2 / attention decoder
configuration (``"family": "hybrid"``, Granite-4.0-H): its seeded
weights and validation tokens, the program's model adapter over them,
the plain reference's accuracy, the oracle's units and the validation's
counts."""
from __future__ import annotations

import math

import jax

from chipbench import harness
from chipbench.reference import hybrid as ref

unit_names = ref.unit_names


def arch_config(cfg: dict):
    """The program's ArchConfig for the configuration file; a program
    without the hybrid layer's settings refuses it here."""
    from repro.configs.base import ArchConfig, SSMConfig
    a = ref.arch(cfg)
    return ArchConfig(
        name="bench-hybrid", family="hybrid", num_layers=a["layers"],
        d_model=a["d"], num_heads=a["heads"], num_kv_heads=a["kv"],
        head_dim=a["hd"], d_ff=a["ff"], vocab_size=a["vocab"],
        qkv_bias=cfg["attention_bias"],
        position_embedding={"nope": "none", "rope": "rope"}[
            cfg["position_embedding_type"]],
        attention_multiplier=a["attn_mult"],
        block_pattern=tuple(a["kinds"]), mlp="swiglu", norm="rmsnorm",
        norm_eps=a["eps"],
        ssm=SSMConfig(d_state=a["d_state"], head_dim=a["ssm_hd"],
                      expand=cfg["mamba_expand"], conv_width=a["conv"],
                      chunk_size=a["chunk"],
                      conv_bias=cfg["mamba_conv_bias"]),
        embedding_multiplier=a["emb_mult"],
        residual_multiplier=a["res_mult"],
        logits_scaling=a["logits_scaling"],
        tie_embeddings=cfg["tie_word_embeddings"], scan_layers=False,
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"])


def make_inputs(cfg: dict, traffic: dict):
    """(params, validation batch) on the device, each from one jitted
    call on the configuration's ``weights.seed``: one model for every
    run seed, as ``families/lm.py`` explains. The tokens come from the
    reference's recurrent form, one position at a time."""
    arch_config(cfg)
    v = traffic["validation"]
    seeds = harness.sub_seeds(cfg["weights"]["seed"])
    params = jax.jit(lambda k: ref.make_params(cfg, k))(
        jax.random.PRNGKey(seeds["weights"]))
    toks = jax.jit(lambda p, k: ref.greedy_tokens(
        cfg, p, k, v["seq"], v["greedy_share"]))(
            params, jax.random.PRNGKey(seeds["data"]))
    return params, {"tokens": toks}


def reference_accuracy_fn(cfg: dict, prec: str):
    """jit((params, validation, keep, w_bits, a_bits) -> accuracy) of the
    plain reference at the precision ``prec``."""
    def f(params, val, keep, wb, ab):
        return ref.accuracy(cfg, params, val["tokens"],
                            ref.policy_arrays(cfg, keep, wb, ab), prec)
    return jax.jit(f)


def oracle_units(cfg: dict) -> list:
    """One dict per compressible unit, in policy order, as the reference
    oracle (``reference/oracle.py``) costs them: a Mamba-2 layer's
    in_proj and out_proj are plain matmuls (``linear``), in_proj pruned
    by whole heads."""
    a = ref.arch(cfg)
    d, ff, H, KV, hd, V = (a["d"], a["ff"], a["heads"], a["kv"], a["hd"],
                           a["vocab"])
    di, nh = a["d_inner"], a["ssm_heads"]
    gran = lambda h: (128 * h // math.gcd(128, h)) // h
    d_proj = 2 * di + 2 * a["d_state"] + nh
    out = [dict(name="embed", kind="embed", i=V, o=d, w=V * d, prune=0,
                gran=1, mix=False)]
    for l, kind in enumerate(a["kinds"]):
        if kind == "attn":
            qkv = (H + 2 * KV) * hd
            out += [
                dict(name=f"L{l}.attn_qkv", kind="qkv", i=d, o=qkv,
                     w=d * qkv, prune=H, gran=gran(hd), hd=hd, kv=KV,
                     mix=d % 256 == 0),
                dict(name=f"L{l}.attn_out", kind="linear", i=H * hd, o=d,
                     w=H * hd * d, prune=0, gran=1, owner=f"L{l}.attn_qkv",
                     mix=(H * hd) % 256 == 0)]
        else:
            out += [
                dict(name=f"L{l}.ssm_in", kind="linear", i=d, o=d_proj,
                     w=d * d_proj, prune=nh, gran=gran(a["ssm_hd"]),
                     mix=d % 256 == 0),
                dict(name=f"L{l}.ssm_out", kind="linear", i=di, o=d,
                     w=di * d, prune=0, gran=1, owner=f"L{l}.ssm_in",
                     mix=di % 256 == 0)]
        out += [
            dict(name=f"L{l}.mlp_up", kind="linear", i=d, o=ff,
                 w=2 * d * ff, prune=ff, gran=128, mix=d % 256 == 0),
            dict(name=f"L{l}.mlp_down", kind="linear", i=ff, o=d,
                 w=ff * d, prune=0, gran=1, owner=f"L{l}.mlp_up",
                 mix=ff % 256 == 0)]
    return out + [dict(name="head", kind="linear", i=d, o=V, w=d * V,
                       prune=0, gran=1, mix=False)]


def _layer_weights(a: dict, kind: str) -> list:
    d, ff, H, KV, hd = a["d"], a["ff"], a["heads"], a["kv"], a["hd"]
    if kind == "attn":
        mixer = [(d, H * hd), (d, KV * hd), (d, KV * hd), (H * hd, d)]
    else:
        mixer = [(d, 2 * a["d_inner"] + 2 * a["d_state"] + a["ssm_heads"]),
                 (a["d_inner"], d)]
    return mixer + [(d, ff), (d, ff), (ff, d)]


def ssd_flops(a: dict, batch: int, seq: int) -> float:
    """The chunked SSD's contractions for ``batch`` sequences of one
    Mamba-2 layer: C.B^T within each chunk (shared by the heads, lower
    triangle with its diagonal), its masked product with x per head, the
    chunk states B^T.x, and the states read out through C."""
    l = min(a["chunk"], seq)
    c = -(-seq // l)
    tri = l * (l + 1) / 2
    h, p, n = a["ssm_heads"], a["ssm_hd"], a["d_state"]
    return 2.0 * batch * c * (tri * n + tri * h * p + 2 * l * h * p * n)


def validation_flops(cfg: dict, traffic: dict) -> float:
    """Forward FLOPs of one policy's validation: every matmul, causal
    attention's lower triangle, and the SSD's contractions."""
    v = traffic["validation"]
    a = ref.arch(cfg)
    batch, seq = len(v["greedy_share"]), v["seq"]
    out = 2.0 * batch * seq * a["d"] * a["vocab"]
    for kind in a["kinds"]:
        out += 2.0 * batch * seq * sum(r * c for r, c in
                                       _layer_weights(a, kind))
        if kind == "attn":
            out += 2 * 2 * batch * a["heads"] * a["hd"] * seq * (seq + 1) / 2
        else:
            out += ssd_flops(a, batch, seq)
    return out


def fake_quant_tensors(cfg: dict, traffic: dict) -> list:
    """(rows, channels, shared) of every distinct tensor one validation
    of a batch of policies fake-quantizes, as ``costs.fake_quant_bytes``
    counts them: each weight (shared by the policies), each distinct
    matmul input of a layer (differs per policy), and the embedding as
    one read of the table (its range) plus the taken rows, read once and
    written once per policy. ``fake_quant_bytes`` counts a shared entry
    1 + K times, so the table's one read is an entry of V / (1 + K)
    rows."""
    v = traffic["validation"]
    K = traffic["episodes_per_batch"]
    a = ref.arch(cfg)
    d, ff, H, hd, V = a["d"], a["ff"], a["heads"], a["hd"], a["vocab"]
    t = len(v["greedy_share"]) * v["seq"]
    acts = {"attn": [(t, d), (t, H * hd), (t, d), (t, ff)],
            "ssm": [(t, d), (t, a["d_inner"]), (t, d), (t, ff)]}
    out = [(V / (1 + K), d, True), (t, d, True)]
    for kind in a["kinds"]:
        out += [(r, c, True) for r, c in _layer_weights(a, kind)]
        out += [(r, c, False) for r, c in acts[kind]]
    return out + [(d, V, True)]


def program_model(cfg: dict, params):
    """The program's compressible-model adapter over the benchmark's
    weights."""
    from repro.core.compress import CompressibleLM
    return CompressibleLM(arch_config(cfg), params)
