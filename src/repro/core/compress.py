"""Apply a compression policy to a model: LayerSpec enumeration per
architecture family, cspec building (quant bits + ℓ1 pruning masks), and
deployment-time weight slicing.

Two model adapters implement the ``CompressibleModel`` protocol used by the
search loop: ``CompressibleLM`` (any ArchConfig) and ``CompressibleResNet``
(the paper's own testbed family).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import pruning
from repro.core.policy import Policy, PolicyBatch
from repro.core.spec import LayerCMP, LayerSpec, effective_bits
from repro.models import blocks as B
from repro.models import model as M
from repro.models import resnet as R


def _lcm(a: int, b: int) -> int:
    return a * b // math.gcd(a, b)


def _head_granularity(head_dim: int, lane: int = 128) -> int:
    return _lcm(lane, head_dim) // head_dim if head_dim else 1


# ===========================================================================
# LayerSpec enumeration for ArchConfig LMs
# ===========================================================================

def _mlp_specs(cfg: ArchConfig, i: int) -> List[LayerSpec]:
    """Layer i's dense FFN units: up (+gate), pruned by ℓ1 channel, and
    down, whose input shrinks with it."""
    d, ff = cfg.d_model, cfg.d_ff
    gated = 2 if cfg.mlp in ("swiglu", "geglu") else 1
    return [
        LayerSpec(name=f"L{i}.mlp_up", kind="mlp_up", layer_idx=i,
                  in_dim=d, out_dim=ff, prunable=True, prune_dim=ff,
                  prune_granularity=128,
                  flops_per_token=2.0 * d * ff * gated,
                  weight_elems=d * ff * gated, act_elems_per_token=d),
        LayerSpec(name=f"L{i}.mlp_down", kind="mlp_down", layer_idx=i,
                  in_dim=ff, out_dim=d, dep_group=f"L{i}.ff",
                  flops_per_token=2.0 * ff * d,
                  weight_elems=ff * d, act_elems_per_token=ff)]


def lm_layer_specs(cfg: ArchConfig) -> List[LayerSpec]:
    specs: List[LayerSpec] = []
    d = cfg.d_model
    if cfg.frontend != "audio_stub":
        specs.append(LayerSpec(
            name="embed", kind="embed", layer_idx=-1, in_dim=cfg.vocab_size,
            out_dim=d, quantizable=True, mix_supported=False,
            weight_elems=cfg.vocab_size * d, act_elems_per_token=1))
    for i, kind in enumerate(cfg.layer_kinds):
        if kind == "attn":
            H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
            specs.append(LayerSpec(
                name=f"L{i}.attn_qkv", kind="attn_qkv", layer_idx=i,
                in_dim=d, out_dim=(H + 2 * KV) * hd,
                prunable=True, prune_dim=H,
                prune_granularity=_head_granularity(hd),
                flops_per_token=2.0 * d * (H + 2 * KV) * hd,
                weight_elems=d * (H + 2 * KV) * hd,
                act_elems_per_token=d,
                extra={"head_dim": hd, "kv_heads": KV}))
            specs.append(LayerSpec(
                name=f"L{i}.attn_out", kind="attn_out", layer_idx=i,
                in_dim=H * hd, out_dim=d, dep_group=f"L{i}.heads",
                flops_per_token=2.0 * H * hd * d,
                weight_elems=H * hd * d, act_elems_per_token=H * hd))
            if cfg.moe is not None:
                E, K, ff = cfg.moe.num_experts, cfg.moe.top_k, cfg.d_ff
                gated = 2
                specs.append(LayerSpec(
                    name=f"L{i}.moe_up", kind="moe_up", layer_idx=i,
                    in_dim=d, out_dim=ff, prunable=True, prune_dim=ff,
                    prune_granularity=128,
                    flops_per_token=2.0 * K * d * ff * gated,
                    weight_elems=E * d * ff * gated, act_elems_per_token=K * d,
                    extra={"experts": E, "top_k": K}))
                specs.append(LayerSpec(
                    name=f"L{i}.moe_down", kind="moe_down", layer_idx=i,
                    in_dim=ff, out_dim=d, dep_group=f"L{i}.moe_ff",
                    flops_per_token=2.0 * K * ff * d,
                    weight_elems=E * ff * d, act_elems_per_token=K * ff,
                    extra={"experts": E, "top_k": K}))
                if cfg.moe.dense_residual:
                    specs.append(LayerSpec(
                        name=f"L{i}.dense_up", kind="mlp_up", layer_idx=i,
                        in_dim=d, out_dim=ff, prunable=True, prune_dim=ff,
                        prune_granularity=128,
                        flops_per_token=2.0 * d * ff * gated,
                        weight_elems=d * ff * gated, act_elems_per_token=d,
                        extra={"dense_residual": True}))
                    specs.append(LayerSpec(
                        name=f"L{i}.dense_down", kind="mlp_down", layer_idx=i,
                        in_dim=ff, out_dim=d, dep_group=f"L{i}.dense_ff",
                        flops_per_token=2.0 * ff * d,
                        weight_elems=ff * d, act_elems_per_token=ff,
                        extra={"dense_residual": True}))
            else:
                specs += _mlp_specs(cfg, i)
        elif kind == "ssm":
            d_inner, nheads, conv_dim = B.ssm_dims(cfg)
            d_proj = 2 * d_inner + 2 * cfg.ssm.d_state + nheads
            specs.append(LayerSpec(
                name=f"L{i}.ssm_in", kind="ssm_in", layer_idx=i,
                in_dim=d, out_dim=d_proj, prunable=True, prune_dim=nheads,
                prune_granularity=_head_granularity(cfg.ssm.head_dim),
                flops_per_token=2.0 * d * d_proj,
                weight_elems=d * d_proj, act_elems_per_token=d,
                extra={"head_dim": cfg.ssm.head_dim,
                       "d_state": cfg.ssm.d_state}))
            specs.append(LayerSpec(
                name=f"L{i}.ssm_out", kind="ssm_out", layer_idx=i,
                in_dim=d_inner, out_dim=d, dep_group=f"L{i}.ssm_heads",
                flops_per_token=2.0 * d_inner * d,
                weight_elems=d_inner * d, act_elems_per_token=d_inner))
            if cfg.mlp != "none":
                specs += _mlp_specs(cfg, i)
        elif kind == "rglru":
            w = cfg.lru_width
            specs.append(LayerSpec(
                name=f"L{i}.rglru_in", kind="rglru_in", layer_idx=i,
                in_dim=d, out_dim=2 * w, prunable=True, prune_dim=w,
                prune_granularity=128,
                flops_per_token=2.0 * d * 2 * w,
                weight_elems=d * 2 * w, act_elems_per_token=d))
            specs.append(LayerSpec(
                name=f"L{i}.rglru_out", kind="rglru_out", layer_idx=i,
                in_dim=w, out_dim=d, dep_group=f"L{i}.lru",
                flops_per_token=2.0 * w * d,
                weight_elems=w * d, act_elems_per_token=w))
            specs += _mlp_specs(cfg, i)
    specs.append(LayerSpec(
        name="head", kind="head", layer_idx=cfg.num_layers,
        in_dim=d, out_dim=cfg.vocab_size, quantizable=True,
        mix_supported=False,
        flops_per_token=2.0 * d * cfg.vocab_size,
        weight_elems=d * cfg.vocab_size, act_elems_per_token=d))
    return specs


# ===========================================================================
# cspec building (quant bits arrays + ℓ1 masks) for LM models
# ===========================================================================

def _qs(cmp: Optional[LayerCMP]):
    """QS dict; missing CMP -> FP32 pass-through (keeps pytree structure
    constant across policies)."""
    w, a = effective_bits(cmp) if cmp is not None else (32, 32)
    return {"w_bits": jnp.int32(w), "a_bits": jnp.int32(a)}


def _layer_params(params, i: int, scanned: bool):
    blocks = params["blocks"]
    if scanned:
        return jax.tree.map(lambda x: x[i], blocks)
    return blocks[i]


def _unit_prune_scores(cfg: ArchConfig, p_l, kind: str,
                       dense: bool = False):
    """ℓ1 scores of one unit's prunable dim — the ONE place the
    per-kind weight/score-function choice lives (shared by the scalar
    cspec builder and the traced batch builder, which must prune
    identical channels)."""
    if kind == "attn_qkv":
        return pruning.head_scores(p_l["attn"]["wq"]["w"], cfg.num_heads)
    if kind == "moe_up":
        return pruning.l1_scores(
            [p_l["moe"]["w_up"], p_l["moe"]["w_gate"]], axis=-1)
    if kind == "mlp_up" and dense:
        return pruning.l1_scores(
            [p_l["moe"]["dense_w_up"], p_l["moe"]["dense_w_gate"]],
            axis=-1)
    if kind == "mlp_up":
        ws = [p_l["mlp"]["w_up"]["w"]]
        if "w_gate" in p_l["mlp"]:
            ws.append(p_l["mlp"]["w_gate"]["w"])
        return pruning.l1_scores(ws)
    if kind == "ssm_in":
        d_inner, nheads, _ = B.ssm_dims(cfg)
        wx = p_l["ssm"]["in_proj"][:, d_inner:2 * d_inner]
        return pruning.head_scores(wx, nheads)
    if kind == "rglru_in":
        return pruning.l1_scores([p_l["rglru"]["w_x"],
                                  p_l["rglru"]["w_y"]])
    return None


def build_lm_cspec(cfg: ArchConfig, params, policy: Policy,
                   specs: Sequence[LayerSpec]) -> dict:
    scanned = cfg.scan_layers and cfg.homogeneous
    by_layer: dict[int, dict[str, LayerCMP]] = {}
    embed_bits = head_bits = None
    for s, c in zip(specs, policy.cmps):
        if s.kind == "embed":
            embed_bits = jnp.int32(effective_bits(c)[0])
        elif s.kind == "head":
            head_bits = jnp.int32(effective_bits(c)[0])
        else:
            by_layer.setdefault(s.layer_idx, {})[s.kind] = c

    def mlp_cs(cm, p_l):
        cu, cd = cm.get("mlp_up"), cm.get("mlp_down")
        ff_mask = None
        if cu is not None and cu.keep < cfg.d_ff:
            scores = _unit_prune_scores(cfg, p_l, "mlp_up")
            ff_mask = pruning.keep_mask(scores, cu.keep)
        return {"up": _qs(cu), "down": _qs(cd), "ff_mask": ff_mask}

    layer_cspecs = []
    for i, kind in enumerate(cfg.layer_kinds):
        p_l = _layer_params(params, i, scanned)
        cm = by_layer.get(i, {})
        cs: dict[str, Any] = {}
        if kind == "attn":
            cq, co = cm.get("attn_qkv"), cm.get("attn_out")
            head_mask = None
            if cq is not None and cq.keep < cfg.num_heads:
                scores = _unit_prune_scores(cfg, p_l, "attn_qkv")
                head_mask = pruning.keep_mask(scores, cq.keep)
            cs["attn"] = {"qkv": _qs(cq),
                          "o": _qs(co),
                          "head_mask": head_mask}
            if cfg.moe is not None:
                cu, cd = cm.get("moe_up"), cm.get("moe_down")
                ff_mask = None
                if cu is not None and cu.keep < cfg.d_ff:
                    scores = _unit_prune_scores(cfg, p_l, "moe_up")
                    ff_mask = pruning.keep_mask(scores, cu.keep)
                moe_cs = {"up": _qs(cu),
                          "down": _qs(cd),
                          "ff_mask": ff_mask,
                          "dense_up": None, "dense_down": None,
                          "dense_ff_mask": None}
                du, dd = cm.get("mlp_up"), cm.get("mlp_down")
                if cfg.moe.dense_residual:
                    dmask = None
                    if du is not None and du.keep < cfg.d_ff:
                        scores = _unit_prune_scores(cfg, p_l, "mlp_up",
                                                    dense=True)
                        dmask = pruning.keep_mask(scores, du.keep)
                    moe_cs["dense_up"] = _qs(du)
                    moe_cs["dense_down"] = _qs(dd)
                    moe_cs["dense_ff_mask"] = dmask
                cs["moe"] = moe_cs
            else:
                cs["mlp"] = mlp_cs(cm, p_l)
        elif kind == "ssm":
            ci, co = cm.get("ssm_in"), cm.get("ssm_out")
            nheads = B.ssm_dims(cfg)[1]
            head_mask = None
            if ci is not None and ci.keep < nheads:
                scores = _unit_prune_scores(cfg, p_l, "ssm_in")
                head_mask = pruning.keep_mask(scores, ci.keep)
            cs["ssm"] = {"in": _qs(ci),
                         "out": _qs(co),
                         "head_mask": head_mask}
            if cfg.mlp != "none":
                cs["mlp"] = mlp_cs(cm, p_l)
        elif kind == "rglru":
            ci, co = cm.get("rglru_in"), cm.get("rglru_out")
            wmask = None
            if ci is not None and ci.keep < cfg.lru_width:
                scores = _unit_prune_scores(cfg, p_l, "rglru_in")
                wmask = pruning.keep_mask(scores, ci.keep)
            cs["rglru"] = {"in": _qs(ci),
                           "out": _qs(co),
                           "width_mask": wmask}
            cs["mlp"] = mlp_cs(cm, p_l)
        layer_cspecs.append(cs)

    if True:  # fill masks for BOTH paths: keeps the cspec pytree structure
        # identical across policies, so one jit compilation serves the
        # whole search (bits/masks are traced values, never shapes).
        def fill_masks(cs_list):
            keys_with_masks = {"attn": ("head_mask", cfg.num_heads),
                               "mlp": ("ff_mask", cfg.d_ff),
                               "moe": ("ff_mask", cfg.d_ff),
                               "ssm": ("head_mask",
                                       B.ssm_dims(cfg)[1] if cfg.ssm else 0),
                               "rglru": ("width_mask", cfg.lru_width)}
            for cs in cs_list:
                for part, (mk, dim) in keys_with_masks.items():
                    if part in cs and cs[part].get(mk) is None and dim:
                        cs[part][mk] = jnp.ones((dim,), jnp.float32)
                if "moe" in cs and cfg.moe and cfg.moe.dense_residual:
                    if cs["moe"].get("dense_ff_mask") is None:
                        cs["moe"]["dense_ff_mask"] = jnp.ones(
                            (cfg.d_ff,), jnp.float32)
            return cs_list

        layer_cspecs = fill_masks(layer_cspecs)
    if scanned:
        blocks_cs = jax.tree.map(lambda *xs: jnp.stack(xs), *layer_cspecs)
    else:
        blocks_cs = layer_cspecs

    out = {"blocks": blocks_cs}
    if embed_bits is not None:
        out["embed_bits"] = embed_bits
    if head_bits is not None:
        out["head_bits"] = head_bits
    return out


# ===========================================================================
# Traced cspec builders — (keep, w_bits, a_bits) arrays -> cspec pytree
# ===========================================================================
#
# build_lm_cspec above runs host-side Python per policy (pytree slicing,
# eager score/mask ops). The builders here move all of that into traced
# jax: prune scores are policy-independent, so they are computed ONCE,
# and the remaining work (bit scalars + rank-based masks) is a pure
# function of the per-unit (keep, w_bits, a_bits) arrays. vmapping the
# builder composed with accuracy gives batched policy evaluation as a
# single jit call — the batched episode engine's validation path.

def _lm_prune_scores(cfg: ArchConfig, params,
                     specs: Sequence[LayerSpec]) -> dict:
    """spec index -> ℓ1 scores of its prunable dim (same
    ``_unit_prune_scores`` selection as build_lm_cspec, evaluated
    eagerly for every prunable unit)."""
    scanned = cfg.scan_layers and cfg.homogeneous
    out: dict[int, jnp.ndarray] = {}
    for idx, s in enumerate(specs):
        if not (s.prunable and s.prune_dim):
            continue
        p_l = _layer_params(params, s.layer_idx, scanned)
        sc = _unit_prune_scores(cfg, p_l, s.kind,
                                dense=bool(s.extra.get("dense_residual")))
        if sc is not None:
            out[idx] = sc
    return out


def make_lm_cspec_builder(cfg: ArchConfig, params,
                          specs: Sequence[LayerSpec]):
    """Returns build(keep, w_bits, a_bits) -> cspec, fully traceable.

    The produced cspec matches build_lm_cspec structurally AND
    numerically for the same policy (masks use the same ℓ1 scores with
    the same tie-breaking), so one jit of accuracy∘build serves every
    policy, and vmap over the arrays batches K policies.
    """
    scanned = cfg.scan_layers and cfg.homogeneous
    scores = _lm_prune_scores(cfg, params, specs)
    pos: dict = {}
    for idx, s in enumerate(specs):
        if s.kind in ("embed", "head"):
            pos[s.kind] = idx
        else:
            pos[(s.layer_idx, s.kind)] = idx

    def build(keep, w_bits, a_bits):
        def qs(key):
            i = pos.get(key)
            if i is None:
                return {"w_bits": jnp.int32(32), "a_bits": jnp.int32(32)}
            return {"w_bits": w_bits[i].astype(jnp.int32),
                    "a_bits": a_bits[i].astype(jnp.int32)}

        def mask(key, dim):
            i = pos.get(key)
            if i is None or i not in scores:
                return jnp.ones((dim,), jnp.float32)
            return pruning.keep_mask_dynamic(scores[i], keep[i])

        def mlp_cs(i):
            return {"up": qs((i, "mlp_up")), "down": qs((i, "mlp_down")),
                    "ff_mask": mask((i, "mlp_up"), cfg.d_ff)}

        layer_cspecs = []
        for i, kind in enumerate(cfg.layer_kinds):
            cs: dict[str, Any] = {}
            if kind == "attn":
                cs["attn"] = {"qkv": qs((i, "attn_qkv")),
                              "o": qs((i, "attn_out")),
                              "head_mask": mask((i, "attn_qkv"),
                                                cfg.num_heads)}
                if cfg.moe is not None:
                    moe_cs = {"up": qs((i, "moe_up")),
                              "down": qs((i, "moe_down")),
                              "ff_mask": mask((i, "moe_up"), cfg.d_ff),
                              "dense_up": None, "dense_down": None,
                              "dense_ff_mask": None}
                    if cfg.moe.dense_residual:
                        moe_cs["dense_up"] = qs((i, "mlp_up"))
                        moe_cs["dense_down"] = qs((i, "mlp_down"))
                        moe_cs["dense_ff_mask"] = mask((i, "mlp_up"),
                                                       cfg.d_ff)
                    cs["moe"] = moe_cs
                else:
                    cs["mlp"] = mlp_cs(i)
            elif kind == "ssm":
                nheads = B.ssm_dims(cfg)[1]
                cs["ssm"] = {"in": qs((i, "ssm_in")),
                             "out": qs((i, "ssm_out")),
                             "head_mask": mask((i, "ssm_in"), nheads)}
                if cfg.mlp != "none":
                    cs["mlp"] = mlp_cs(i)
            elif kind == "rglru":
                cs["rglru"] = {"in": qs((i, "rglru_in")),
                               "out": qs((i, "rglru_out")),
                               "width_mask": mask((i, "rglru_in"),
                                                  cfg.lru_width)}
                cs["mlp"] = mlp_cs(i)
            layer_cspecs.append(cs)
        if scanned:
            blocks_cs = jax.tree.map(lambda *xs: jnp.stack(xs),
                                     *layer_cspecs)
        else:
            blocks_cs = layer_cspecs
        out = {"blocks": blocks_cs}
        if "embed" in pos:
            out["embed_bits"] = w_bits[pos["embed"]].astype(jnp.int32)
        if "head" in pos:
            out["head_bits"] = w_bits[pos["head"]].astype(jnp.int32)
        return out

    return build


def make_resnet_cspec_builder(cmodel: "CompressibleResNet"):
    """ResNet analogue of make_lm_cspec_builder."""
    specs = cmodel.specs
    scores: dict[int, jnp.ndarray] = {}
    conv_i = 0
    for idx, s in enumerate(specs):
        if s.kind == "conv":
            if s.prunable:
                scores[idx] = pruning.l1_scores(
                    [cmodel._conv_weight(conv_i)])
            conv_i += 1

    def build(keep, w_bits, a_bits):
        cspec = []
        for idx, s in enumerate(specs):
            entry: dict[str, Any] = {"qs": None, "mask": None}
            if s.quantizable:
                entry["qs"] = {"w_bits": w_bits[idx].astype(jnp.int32),
                               "a_bits": a_bits[idx].astype(jnp.int32)}
            if idx in scores:
                entry["mask"] = pruning.keep_mask_dynamic(scores[idx],
                                                          keep[idx])
            cspec.append(entry)
        return cspec

    return build


# ===========================================================================
# Model adapters (protocol used by the search / sensitivity analysis)
# ===========================================================================

def stack_cspecs(cspecs: Sequence[Any]):
    """Stack K cspec pytrees along a new leading axis.

    cspecs are policy-independent in structure (masks always
    materialized, bits always present — see build_lm_cspec), so K of
    them stack leaf-wise into one batch a single vmapped evaluation can
    consume.
    """
    return jax.tree.map(lambda *xs: jnp.stack(xs), *cspecs)


class _BatchedAccuracyMixin:
    """Batched accuracy evaluation, shared by both adapters."""

    def cspec_builder(self):
        """The traced cspec builder for the CURRENT params, cached per
        params identity — ONE builder (and one eager prune-score pass)
        shared by the batched/fused validators (``accuracy_policy_fn``)
        and the fused sensitivity analysis (``core.sensitivity``)."""
        cached = getattr(self, "_builder_cache", None)
        if cached is None or cached[0] is not self.params:
            self._builder_cache = (self.params, self._make_cspec_builder())
        return self._builder_cache[1]

    def build_cspec_batch(self, policies: Sequence[Policy]):
        return stack_cspecs([self.build_cspec(p) for p in policies])

    def accuracy_batch(self, batch: dict, stacked_cspec) -> jnp.ndarray:
        """(K,) accuracies for K stacked cspecs — one vmap-of-jit call
        instead of K sequential jit dispatches."""
        return self._acc_batch_fn(batch)(self.params, stacked_cspec)

    def _acc_batch_fn(self, batch: dict):
        cached = getattr(self, "_acc_batch_cache", None)
        if cached is not None and cached[0] is batch:
            return cached[1]
        fn = jax.jit(jax.vmap(lambda p, cs: self.accuracy(batch, cs, p),
                              in_axes=(None, 0)))
        self._acc_batch_cache = (batch, fn)
        return fn

    def accuracy_policy_fn(self, batch: dict):
        """The pure traced-cspec validator: ``(params, keep, w_bits,
        a_bits)`` with (K, L) int32 policy arrays -> (K,) accuracies,
        un-jitted so callers can inline it into a larger traced program
        (the search's epoch program chains it inside its ``lax.scan``
        body).

        The weights are an argument, never a closed-over constant: a
        jitted program that captured them would embed them in its HLO
        (gigabytes at real model widths). ``accuracy_policy_batch`` jits
        exactly this function; both share one cache keyed on batch AND
        params identity, because the traced builder bakes the ℓ1 prune
        scores of the current weights in as constants — swapping in new
        weights (e.g. after a QAT retrain) must re-trace.
        """
        cached = getattr(self, "_acc_pb_cache", None)
        if cached is None or cached[0] is not batch \
                or cached[3] is not self.params:
            build = self.cspec_builder()
            fn = jax.vmap(
                lambda p, k, w, a: self.accuracy(batch, build(k, w, a), p),
                in_axes=(None, 0, 0, 0))
            self._acc_pb_cache = (batch, fn, jax.jit(fn), self.params)
            cached = self._acc_pb_cache
        return cached[1]

    def accuracy_policy_batch(self, batch: dict,
                              pbatch: "PolicyBatch") -> jnp.ndarray:
        """(K,) accuracies straight from PolicyBatch arrays.

        The traced cspec builder fuses into the vmapped accuracy, so
        the whole validation (mask building included) is ONE jit call —
        no per-policy host-side cspec construction at all.
        """
        self.accuracy_policy_fn(batch)        # (re)fill the shared cache
        return self._acc_pb_cache[2](self.params,
                                     jnp.asarray(pbatch.keep, jnp.int32),
                                     jnp.asarray(pbatch.w_bits, jnp.int32),
                                     jnp.asarray(pbatch.a_bits, jnp.int32))


@dataclass
class CompressibleLM(_BatchedAccuracyMixin):
    """Adapter: ArchConfig LM + params + data -> the search interface."""
    cfg: ArchConfig
    params: Any

    def __post_init__(self):
        self.specs = lm_layer_specs(self.cfg)

    def build_cspec(self, policy: Policy):
        return build_lm_cspec(self.cfg, self.params, policy, self.specs)

    def _make_cspec_builder(self):
        return make_lm_cspec_builder(self.cfg, self.params, self.specs)

    def logits(self, batch: dict, cspec=None, params=None):
        """``params`` overrides ``self.params``: jitted callers pass the
        weights in rather than capture them as constants."""
        params = self.params if params is None else params
        return M.forward(self.cfg, params, tokens=batch["tokens"],
                         cspec=cspec)

    def log_probs(self, batch: dict, cspec=None, params=None):
        return jax.nn.log_softmax(self.logits(batch, cspec, params), -1)

    def accuracy(self, batch: dict, cspec=None, params=None) -> jnp.ndarray:
        """Next-token top-1 accuracy."""
        lg = self.logits(batch, cspec, params)[:, :-1]
        tgt = batch["tokens"][:, 1:]
        return jnp.mean((jnp.argmax(lg, -1) == tgt).astype(jnp.float32))


@dataclass
class CompressibleResNet(_BatchedAccuracyMixin):
    cfg: R.ResNetConfig
    params: Any

    def __post_init__(self):
        self.specs = R.layer_specs(self.cfg)

    def build_cspec(self, policy: Policy):
        cspec = []
        conv_i = 0
        convs = list(R._iter_convs(self.cfg))
        for s, c in zip(self.specs, policy.cmps):
            entry: dict[str, Any] = {"qs": _qs(c) if s.quantizable else None,
                                     "mask": None}
            if s.kind == "conv":
                if s.prunable:
                    # always materialize a mask (ones when unpruned) so the
                    # cspec structure is policy-independent -> one jit cache
                    w = self._conv_weight(conv_i)
                    scores = pruning.l1_scores([w])
                    entry["mask"] = pruning.keep_mask(scores, c.keep)
                conv_i += 1
            cspec.append(entry)
        return cspec

    def _make_cspec_builder(self):
        return make_resnet_cspec_builder(self)

    def _conv_weight(self, idx: int):
        i = 0
        if idx == 0:
            return self.params["stem"]["w"]
        i = 1
        for blocks in self.params["stages"]:
            for blk in blocks:
                for key in ("conv1", "conv2", "skip"):
                    if key in blk:
                        if i == idx:
                            return blk[key]["w"]
                        i += 1
        raise IndexError(idx)

    def logits(self, batch: dict, cspec=None, params=None):
        params = self.params if params is None else params
        return R.forward(self.cfg, params, batch["images"], cspec)

    def log_probs(self, batch: dict, cspec=None, params=None):
        return jax.nn.log_softmax(self.logits(batch, cspec, params), -1)

    def accuracy(self, batch: dict, cspec=None, params=None) -> jnp.ndarray:
        lg = self.logits(batch, cspec, params)
        return jnp.mean((jnp.argmax(lg, -1) == batch["labels"])
                        .astype(jnp.float32))


# ===========================================================================
# Deployment: materialize truly sliced weights (unrolled LMs / ResNet)
# ===========================================================================

def slice_lm_params(cfg: ArchConfig, params, cspec) -> Any:
    """Slice pruned channels out for deployment (unrolled models only).
    Returns a new params pytree with reduced shapes."""
    if cfg.scan_layers and cfg.homogeneous:
        raise ValueError("slice requires an unrolled model; set "
                         "scan_layers=False for deployment")
    new = {k: v for k, v in params.items() if k != "blocks"}
    new_blocks = []
    for i, (p_l, cs) in enumerate(zip(params["blocks"], cspec["blocks"])):
        p_l = jax.tree.map(lambda x: x, p_l)  # shallow copy
        kind = cfg.layer_kinds[i]
        if kind == "attn" and cs.get("attn", {}).get("head_mask") is not None:
            hm = cs["attn"]["head_mask"]
            idx = pruning.slice_indices(hm)
            hd = cfg.head_dim
            cols = np.concatenate([np.arange(h * hd, (h + 1) * hd)
                                   for h in idx])
            a = p_l["attn"]
            a["wq"]["w"] = a["wq"]["w"][:, cols]
            if "b" in a["wq"]:
                a["wq"]["b"] = a["wq"]["b"][cols]
            a["wo"]["w"] = a["wo"]["w"][cols, :]
        mlp_cs = cs.get("mlp")
        if mlp_cs is not None and mlp_cs.get("ff_mask") is not None:
            idx = pruning.slice_indices(mlp_cs["ff_mask"])
            m = p_l["mlp"]
            m["w_up"]["w"] = m["w_up"]["w"][:, idx]
            if "w_gate" in m:
                m["w_gate"]["w"] = m["w_gate"]["w"][:, idx]
            m["w_down"]["w"] = m["w_down"]["w"][idx, :]
        new_blocks.append(p_l)
    new["blocks"] = new_blocks
    return new
