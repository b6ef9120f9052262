"""Full language-model assembly for all assigned architectures.

``init(cfg, key)``            -> params pytree (scan-stacked when homogeneous)
``forward(cfg, params, ...)`` -> logits  (train / prefill)
``init_cache(cfg, batch, max_len)``
``decode_step(cfg, params, cache, tokens, pos)`` -> (logits, cache)

Compression: every entry point takes ``cspec`` (see ``repro/core/compress``)
— quant bit widths and pruning masks that flow through the stacked layers.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from repro.models import blocks as B
from repro.models import layers as L


# ---------------------------------------------------------------------------
# Per-kind block init/apply/decode/cache dispatch
# ---------------------------------------------------------------------------

def _init_block(kind: str, key, cfg: ArchConfig, dtype):
    ks = jax.random.split(key, 4)
    if kind == "attn":
        p = {"attn_norm": L.norm_init(cfg.norm, cfg.d_model, dtype),
             "attn": B.init_attention(ks[0], cfg, dtype),
             "mlp_norm": L.norm_init(cfg.norm, cfg.d_model, dtype)}
        if cfg.moe is not None:
            p["moe"] = B.init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"] = B.init_mlp(ks[1], cfg, dtype)
        return p
    if kind == "ssm":
        p = {"norm": L.norm_init(cfg.norm, cfg.d_model, dtype),
             "ssm": B.init_ssm(ks[0], cfg, dtype)}
        if cfg.mlp != "none":
            p["mlp_norm"] = L.norm_init(cfg.norm, cfg.d_model, dtype)
            p["mlp"] = B.init_mlp(ks[1], cfg, dtype)
        return p
    if kind == "rglru":
        return {"mix_norm": L.norm_init(cfg.norm, cfg.d_model, dtype),
                "rglru": B.init_rglru(ks[0], cfg, dtype),
                "mlp_norm": L.norm_init(cfg.norm, cfg.d_model, dtype),
                "mlp": B.init_mlp(ks[1], cfg, dtype)}
    raise ValueError(kind)


def _norm(cfg: ArchConfig, p, x):
    return L.apply_norm(cfg.norm, p, x, cfg.norm_eps)


def _residual(cfg: ArchConfig, x, out):
    """x + out, the sublayer's output scaled by the residual multiplier."""
    if cfg.residual_multiplier != 1.0:
        out = out * cfg.residual_multiplier
    return x + out


def _ffn(p, x, cfg: ArchConfig, cs):
    """The block's FFN sublayer (MLP or MoE), if it has one."""
    if "mlp" not in p and "moe" not in p:
        return x
    h = _norm(cfg, p["mlp_norm"], x)
    if "moe" in p:
        return _residual(cfg, x, B.apply_moe(p["moe"], h, cfg, cs.get("moe")))
    return _residual(cfg, x, B.apply_mlp(p["mlp"], h, cfg, cs.get("mlp")))


def _apply_block(kind: str, p, x, cfg: ArchConfig, cspec, positions):
    cs = cspec or {}
    if kind == "attn":
        h = _norm(cfg, p["attn_norm"], x)
        x = _residual(cfg, x, B.apply_attention(p["attn"], h, cfg,
                                                cs.get("attn"), positions))
    elif kind == "ssm":
        h = _norm(cfg, p["norm"], x)
        x = _residual(cfg, x, B.apply_ssm(p["ssm"], h, cfg, cs.get("ssm")))
    elif kind == "rglru":
        h = _norm(cfg, p["mix_norm"], x)
        x = _residual(cfg, x, B.apply_rglru(p["rglru"], h, cfg,
                                            cs.get("rglru")))
    else:
        raise ValueError(kind)
    return _ffn(p, x, cfg, cs)


def _init_block_cache(kind: str, cfg: ArchConfig, batch: int, max_len: int,
                      dtype, cache_bits: int = 16):
    if kind == "attn":
        return B.init_attn_cache(cfg, batch, max_len, dtype, cache_bits)
    if kind == "ssm":
        return B.init_ssm_cache(cfg, batch, dtype)
    if kind == "rglru":
        return B.init_rglru_cache(cfg, batch, dtype)
    raise ValueError(kind)


def _decode_block(kind: str, p, x, cache, pos, cfg: ArchConfig, cspec):
    cs = cspec or {}
    if kind == "attn":
        h = _norm(cfg, p["attn_norm"], x)
        o, cache = B.decode_attention_block(p["attn"], h, cache, pos, cfg,
                                            cs.get("attn"))
    elif kind == "ssm":
        h = _norm(cfg, p["norm"], x)
        o, cache = B.decode_ssm(p["ssm"], h, cache, pos, cfg, cs.get("ssm"))
    elif kind == "rglru":
        h = _norm(cfg, p["mix_norm"], x)
        o, cache = B.decode_rglru(p["rglru"], h, cache, pos, cfg,
                                  cs.get("rglru"))
    else:
        raise ValueError(kind)
    return _ffn(p, _residual(cfg, x, o), cfg, cs), cache


# ---------------------------------------------------------------------------
# Model init
# ---------------------------------------------------------------------------

def init(cfg: ArchConfig, key) -> dict:
    dtype = L.dtype_of(cfg.param_dtype)
    keys = jax.random.split(key, cfg.num_layers + 3)
    params: dict[str, Any] = {}
    if cfg.frontend != "audio_stub":
        params["embed"] = (jax.random.normal(
            keys[-1], (cfg.vocab_size, cfg.d_model), jnp.float32)
            / (cfg.d_model ** 0.5)).astype(dtype)
    if cfg.scan_layers and cfg.homogeneous:
        kind = cfg.layer_kinds[0]
        per_layer = [_init_block(kind, keys[i], cfg, dtype)
                     for i in range(cfg.num_layers)]
        params["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    else:
        params["blocks"] = [
            _init_block(cfg.layer_kinds[i], keys[i], cfg, dtype)
            for i in range(cfg.num_layers)]
    params["final_norm"] = L.norm_init(cfg.norm, cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["unembed"] = L.linear_init(keys[-2], cfg.d_model,
                                          cfg.vocab_size, dtype)["w"]
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _embed_inputs(cfg: ArchConfig, params, tokens, embeds, cspec):
    ebits = None if cspec is None else cspec.get("embed_bits")
    if cfg.frontend == "audio_stub":
        return embeds  # [B, S, d] straight from the (stub) frontend
    dtype = L.dtype_of(cfg.compute_dtype)
    table = L.getw(params, "embed", dtype)
    if ebits is not None:
        # only the looked-up rows are fake-quantized (with the table's
        # range): the same values as quantizing the whole table first
        x = L.fq_rows(table, tokens, ebits, dtype)
    else:
        x = jnp.take(table, tokens, axis=0)
    x = x.astype(dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    if cfg.frontend == "vision_stub" and embeds is not None:
        P = embeds.shape[1]
        x = jnp.concatenate([embeds.astype(x.dtype), x[:, P:]], axis=1)
    return x


def _unembed(cfg: ArchConfig, params, x, cspec):
    hbits = None if cspec is None else cspec.get("head_bits")
    if cfg.tie_embeddings:
        w = L.getw(params, "embed", x.dtype).T
    else:
        w = L.getw(params, "unembed", x.dtype)
    if hbits is not None:
        w = L.fq_weight(w, hbits, x.dtype)
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
    logits = logits.astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return shard(logits, "batch", "seq", "vocab")


def forward(cfg: ArchConfig, params, tokens=None, embeds=None, cspec=None,
            positions=None) -> jnp.ndarray:
    """Returns logits [B, S, vocab] (f32)."""
    x = _embed_inputs(cfg, params, tokens, embeds, cspec)
    x = shard(x, "batch", "seq", "embed")
    S = x.shape[1]
    if positions is None:
        positions = jnp.arange(S)[None, :]
    blocks_cs = None if cspec is None else cspec.get("blocks")

    if cfg.scan_layers and cfg.homogeneous:
        kind = cfg.layer_kinds[0]

        def body(h, layer):
            p_l, cs_l = layer
            h = _apply_block(kind, p_l, h, cfg, cs_l, positions)
            return h, None

        if cfg.remat != "none":
            policy = (jax.checkpoint_policies.nothing_saveable
                      if cfg.remat == "full"
                      else jax.checkpoint_policies.dots_saveable)
            body = jax.checkpoint(body, policy=policy)
        x, _ = jax.lax.scan(body, x, (params["blocks"], blocks_cs))
    else:
        for i, p_l in enumerate(params["blocks"]):
            cs_l = None if blocks_cs is None else blocks_cs[i]
            fn = functools.partial(_apply_block, cfg.layer_kinds[i])
            if cfg.remat != "none":
                fn = jax.checkpoint(
                    fn, policy=jax.checkpoint_policies.nothing_saveable
                    if cfg.remat == "full"
                    else jax.checkpoint_policies.dots_saveable,
                    static_argnums=(2,))   # cfg is static
            x = fn(p_l, x, cfg, cs_l, positions)
    x = _norm(cfg, params["final_norm"], x)
    return _unembed(cfg, params, x, cspec)


# ---------------------------------------------------------------------------
# Decode (single new token against a cache)
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=None, cache_bits: int = 16) -> dict:
    dtype = dtype or L.dtype_of(cfg.compute_dtype)
    if cfg.scan_layers and cfg.homogeneous:
        kind = cfg.layer_kinds[0]
        per_layer = [_init_block_cache(kind, cfg, batch, max_len, dtype,
                                       cache_bits)
                     for _ in range(cfg.num_layers)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    return [_init_block_cache(cfg.layer_kinds[i], cfg, batch, max_len, dtype,
                              cache_bits)
            for i in range(cfg.num_layers)]


def decode_step(cfg: ArchConfig, params, cache, tokens, pos, cspec=None,
                embeds=None):
    """tokens: [B, 1] (or embeds for audio); pos: scalar int. Returns
    (logits [B, 1, V], new_cache)."""
    x = _embed_inputs(cfg, params, tokens, embeds, cspec)
    x = shard(x, "batch", "seq", "embed")
    blocks_cs = None if cspec is None else cspec.get("blocks")

    if cfg.scan_layers and cfg.homogeneous:
        kind = cfg.layer_kinds[0]

        def body(h, layer):
            p_l, c_l, cs_l = layer
            h, new_c = _decode_block(kind, p_l, h, c_l, pos, cfg, cs_l)
            return h, new_c

        x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache,
                                              blocks_cs))
    else:
        new_cache = []
        for i, (p_l, c_l) in enumerate(zip(params["blocks"], cache)):
            cs_l = None if blocks_cs is None else blocks_cs[i]
            x, c = _decode_block(cfg.layer_kinds[i], p_l, x, c_l, pos, cfg,
                                 cs_l)
            new_cache.append(c)
    x = _norm(cfg, params["final_norm"], x)
    return _unembed(cfg, params, x, cspec), new_cache
