"""What the search driver needs of a decoder-only language model
configuration (``"family": "lm"``): its seeded weights and validation
tokens, the program's model adapter over them, the plain reference's
accuracy, and the validation's counts."""
from __future__ import annotations

import math

import jax

from chipbench import costs, harness
from chipbench.reference import lm as ref_lm


def make_inputs(cfg: dict, traffic: dict):
    """(params, validation batch) on the device, each from one jitted
    call on the configuration's ``weights.seed``.

    The model and its validation rows are the same for every run seed,
    as a user compresses one model with many search seeds; the run seed
    drives the search. The program compiles the validation tokens, the
    weights' pruning scores and the sensitivity measured on them into
    its epoch and sensitivity programs as constants, so a model drawn
    from the run seed would compile those programs anew on every seed.
    """
    v = traffic["validation"]
    seeds = harness.sub_seeds(cfg["weights"]["seed"])
    params = jax.jit(lambda k: ref_lm.make_params(cfg, k))(
        jax.random.PRNGKey(seeds["weights"]))
    toks = jax.jit(lambda p, k: ref_lm.greedy_tokens(
        cfg, p, k, v["seq"], v["greedy_share"]))(
            params, jax.random.PRNGKey(seeds["data"]))
    return params, {"tokens": toks}


def reference_accuracy_fn(cfg: dict, prec: str):
    """jit((params, validation, keep, w_bits, a_bits) -> accuracy) of the
    plain reference at the precision ``prec``."""
    def f(params, val, keep, wb, ab):
        return ref_lm.accuracy(cfg, params, val["tokens"],
                               ref_lm.policy_arrays(cfg, keep, wb, ab), prec)
    return jax.jit(f)


unit_names = ref_lm.unit_names


def oracle_units(cfg: dict) -> list:
    """One dict per compressible unit, in policy order, as the reference
    oracle (``reference/oracle.py``) costs them."""
    a = ref_lm.arch(cfg)
    d, ff, H, KV, hd, V = (a["d"], a["ff"], a["heads"], a["kv"], a["hd"],
                           a["vocab"])
    gran = (128 * hd // math.gcd(128, hd)) // hd
    out = [dict(name="embed", kind="embed", i=V, o=d, w=V * d, prune=0,
                gran=1, mix=False)]
    for l in range(a["layers"]):
        qkv = (H + 2 * KV) * hd
        out += [
            dict(name=f"L{l}.attn_qkv", kind="qkv", i=d, o=qkv, w=d * qkv,
                 prune=H, gran=gran, hd=hd, kv=KV, mix=d % 256 == 0),
            dict(name=f"L{l}.attn_out", kind="linear", i=H * hd, o=d,
                 w=H * hd * d, prune=0, gran=1, owner=f"L{l}.attn_qkv",
                 mix=(H * hd) % 256 == 0),
            dict(name=f"L{l}.mlp_up", kind="linear", i=d, o=ff,
                 w=2 * d * ff, prune=ff, gran=128, mix=d % 256 == 0),
            dict(name=f"L{l}.mlp_down", kind="linear", i=ff, o=d,
                 w=ff * d, prune=0, gran=1, owner=f"L{l}.mlp_up",
                 mix=ff % 256 == 0)]
    return out + [dict(name="head", kind="linear", i=d, o=V, w=d * V,
                       prune=0, gran=1, mix=False)]


def validation_flops(cfg: dict, traffic: dict) -> float:
    v = traffic["validation"]
    return costs.lm_forward_flops(cfg, len(v["greedy_share"]), v["seq"])


def fake_quant_tensors(cfg: dict, traffic: dict) -> list:
    v = traffic["validation"]
    return costs.fake_quant_tensors_lm(cfg, len(v["greedy_share"]),
                                       v["seq"])


def arch_config(cfg: dict):
    """The program's ArchConfig for the configuration file."""
    from repro.configs.base import ArchConfig
    a = ref_lm.arch(cfg)
    return ArchConfig(
        name="bench-lm", family="dense", num_layers=a["layers"],
        d_model=a["d"], num_heads=a["heads"], num_kv_heads=a["kv"],
        head_dim=a["hd"], d_ff=a["ff"], vocab_size=a["vocab"],
        qkv_bias=cfg["attention_bias"], rope_theta=a["theta"],
        mlp="swiglu", norm="rmsnorm",
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"])


def program_model(cfg: dict, params):
    """The program's compressible-model adapter over the benchmark's
    weights."""
    from repro.core.compress import CompressibleLM
    return CompressibleLM(arch_config(cfg), params)
