"""Plain reference of the Qwen2-style decoder the search compresses:
token embedding, per layer RMSNorm -> GQA attention with RoPE and q/k/v
biases -> residual -> RMSNorm -> SwiGLU MLP -> residual, final RMSNorm,
and a head tied to the embedding table (Qwen2 report, arXiv:2407.10671).

A compression policy enters as per-unit arrays: ``embed`` and ``head``
quantize their weight table; each layer's ``qkv`` unit quantizes the
input and the q/k/v weights and keeps its ``keep`` heads with the
largest l1 norm of their query columns; ``out`` quantizes the attention
output projection; ``up`` quantizes the up and gate projections and
keeps the ``keep`` feed-forward channels with the largest summed l1
norm of their up and gate columns; ``down`` quantizes the down
projection. Pruning multiplies the pruned heads' attention output and
the pruned channels' activations by zero.

Also here: the seeded weights the benchmark serves to the program, and
the seeded validation tokens.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.compression import fake_quant, keep_mask
from chipbench.reference.numerics import Numerics

NEG_INF = -1e30


def arch(cfg: dict) -> dict:
    """Shape facts of a configuration file, under short names."""
    return {"d": cfg["hidden_size"], "ff": cfg["intermediate_size"],
            "layers": cfg["num_hidden_layers"],
            "heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "vocab": cfg["vocab_size"], "theta": cfg["rope_theta"],
            "eps": cfg["rms_norm_eps"]}


def unit_names(cfg: dict) -> list:
    """The compressible units in the order the policy arrays use."""
    a = arch(cfg)
    names = ["embed"]
    for i in range(a["layers"]):
        names += [f"L{i}.attn_qkv", f"L{i}.attn_out", f"L{i}.mlp_up",
                  f"L{i}.mlp_down"]
    return names + ["head"]


def make_params(cfg: dict, key):
    """Seeded float32 weights in the layout the program consumes (layers
    stacked on a leading axis), shaped by the configuration's
    ``weights`` entry:

    * normal weights scaled by 1/sqrt(fan-in), small normal biases,
      norm scales near 1; the residual branches' output projections
      (attention out, MLP down) further scaled by ``branch_out_scale``
      (GPT-2's 1/sqrt(2 * layers)), so the residual stream keeps the
      token's embedding;
    * embedding rows whose norms spread log-uniformly over
      ``embedding_row_norms``: the tied head then favours a position's
      own token by a margin that grows with its norm, so greedy
      choices range from confident to borderline, as a trained model's
      do, and part of them survive compression;
    * channel 0 a massive activation of the head's input: the final
      norm scales it by ``massive_final_norm_scale`` while the tied
      embedding's column 0 is zero, so the head does not read it.
      Float arithmetic at any precision gives the same logits with or
      without it; per-token int8 activations lose every other channel
      to it (outlier features, arXiv:2208.07339; massive activations,
      arXiv:2402.17762).
    """
    a = arch(cfg)
    d, ff, L, H, KV, hd, V = (a["d"], a["ff"], a["layers"], a["heads"],
                              a["kv"], a["hd"], a["vocab"])
    wc = cfg["weights"]
    ks = iter(jax.random.split(key, 20))

    def w(shape, fan, gain=1.0):
        return jax.random.normal(next(ks), shape, jnp.float32) * (
            gain / math.sqrt(fan))

    def b(shape):
        return 0.02 * jax.random.normal(next(ks), shape, jnp.float32)

    def scale(shape):
        return 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)

    lo, hi = wc["embedding_row_norms"]
    norms = lo * (hi / lo) ** jax.random.uniform(next(ks), (V, 1))
    embed = (w((V, d), d) * norms).at[:, 0].set(0.0)
    g_out = wc["branch_out_scale"]
    return {
        "embed": embed,
        "blocks": {
            "attn_norm": {"scale": scale((L, d))},
            "attn": {"wq": {"w": w((L, d, H * hd), d), "b": b((L, H * hd))},
                     "wk": {"w": w((L, d, KV * hd), d), "b": b((L, KV * hd))},
                     "wv": {"w": w((L, d, KV * hd), d), "b": b((L, KV * hd))},
                     "wo": {"w": w((L, H * hd, d), H * hd, g_out)}},
            "mlp_norm": {"scale": scale((L, d))},
            "mlp": {"w_up": {"w": w((L, d, ff), d)},
                    "w_gate": {"w": w((L, d, ff), d)},
                    "w_down": {"w": w((L, ff, d), ff, g_out)}}},
        "final_norm": {"scale": scale((d,)).at[0].set(
            wc["massive_final_norm_scale"])},
    }


def policy_arrays(cfg: dict, keep, wb, ab) -> dict:
    """(units,) policy arrays in ``unit_names`` order -> the per-kind
    arrays ``logits`` takes."""
    L = arch(cfg)["layers"]
    per = lambda x, j: jnp.asarray(x)[1 + j:1 + 4 * L:4]
    return {"embed": wb[0], "head": wb[-1],
            "qkv": (per(keep, 0), per(wb, 0), per(ab, 0)),
            "out": (per(wb, 1), per(ab, 1)),
            "up": (per(keep, 2), per(wb, 2), per(ab, 2)),
            "down": (per(wb, 3), per(ab, 3))}


def _rms(x, scale, eps, num):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return num.cast(y * scale)


def _rope(x, theta):
    S, half = x.shape[1], x.shape[-1] // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _proj(x, w, wbits, abits, num, q=True):
    if q:
        x = fake_quant(x, abits, num)
        w = fake_quant(w, wbits, num)
    return num.mm("bsi,io->bso", num.cast(x), num.cast(w))


def logits(cfg: dict, params, tokens, pol, prec: str = "f32"):
    """[B, S, vocab] float32 logits of ``tokens`` under the policy arrays
    ``pol`` (``policy_arrays``; None = uncompressed)."""
    num = Numerics(prec)
    a = arch(cfg)
    H, KV, hd, eps = a["heads"], a["kv"], a["hd"], a["eps"]
    G = H // KV
    q_on = pol is not None
    table = params["embed"]
    if q_on:
        table = fake_quant(table, pol["embed"], num)
    x = num.cast(jnp.take(table, tokens, axis=0))
    B, S, _ = x.shape
    causal = jnp.tril(jnp.ones((S, S), bool))
    full = lambda n: jnp.full((a["layers"],), n, jnp.int32)
    lp = pol if q_on else {
        "qkv": (full(H), full(32), full(32)), "out": (full(32), full(32)),
        "up": (full(a["ff"]), full(32), full(32)),
        "down": (full(32), full(32))}

    def layer(x, xs):
        p, (qk, qw, qa), (ow, oa), (uk, uw, ua), (dw, da) = xs
        at = p["attn"]
        h = _rms(x, p["attn_norm"]["scale"], eps, num)
        q = _proj(h, at["wq"]["w"], qw, qa, num, q_on) + num.cast(at["wq"]["b"])
        k = _proj(h, at["wk"]["w"], qw, qa, num, q_on) + num.cast(at["wk"]["b"])
        v = _proj(h, at["wv"]["w"], qw, qa, num, q_on) + num.cast(at["wv"]["b"])
        q = num.cast(_rope(q.reshape(B, S, H, hd), a["theta"]))
        k = num.cast(_rope(k.reshape(B, S, KV, hd), a["theta"]))
        v = v.reshape(B, S, KV, hd)
        qg = q.reshape(B, S, KV, G, hd)
        s = num.mm("bqkgd,blkd->bkgql", qg, k).astype(jnp.float32)
        s = jnp.where(causal, s / math.sqrt(hd), NEG_INF)
        pr = jax.nn.softmax(s, -1)
        o = num.mm("bkgql,blkd->bqkgd", num.cast(pr), v).reshape(B, S, H, hd)
        hscore = jnp.sum(jnp.abs(at["wq"]["w"]).reshape(-1, H, hd), (0, 2))
        o = o * num.cast(keep_mask(hscore, qk))[None, None, :, None]
        x = x + _proj(o.reshape(B, S, H * hd), at["wo"]["w"], ow, oa, num,
                      q_on)
        h = _rms(x, p["mlp_norm"]["scale"], eps, num)
        m = p["mlp"]
        up = _proj(h, m["w_up"]["w"], uw, ua, num, q_on)
        gate = _proj(h, m["w_gate"]["w"], uw, ua, num, q_on)
        fscore = (jnp.sum(jnp.abs(m["w_up"]["w"]), 0)
                  + jnp.sum(jnp.abs(m["w_gate"]["w"]), 0))
        act = num.cast(jax.nn.silu(gate.astype(jnp.float32))
                       * up.astype(jnp.float32)) \
            * num.cast(keep_mask(fscore, uk))
        x = x + _proj(act, m["w_down"]["w"], dw, da, num, q_on)
        return x, None

    xs = (params["blocks"], lp["qkv"], lp["out"], lp["up"], lp["down"])
    x, _ = jax.lax.scan(layer, x, xs)
    x = _rms(x, params["final_norm"]["scale"], eps, num)
    head = params["embed"].T
    if q_on:
        head = fake_quant(head, pol["head"], num)
    return num.mm("bsd,dv->bsv", x, num.cast(head)).astype(jnp.float32)


def accuracy(cfg: dict, params, tokens, pol, prec: str = "f32"):
    """Next-token top-1 accuracy of the compressed model."""
    lg = logits(cfg, params, tokens, pol, prec)[:, :-1]
    return jnp.mean((jnp.argmax(lg, -1) == tokens[:, 1:]).astype(jnp.float32))


def greedy_tokens(cfg: dict, params, key, seq: int, greedy_share):
    """Seeded validation tokens, one row per entry of ``greedy_share``:
    the first token and a random ``1 - share`` of the rest of a row are
    uniform draws; every other next token is the uncompressed reference
    model's greedy choice."""
    V = arch(cfg)["vocab"]
    share = jnp.asarray(greedy_share, jnp.float32)[:, None]
    batch = share.shape[0]
    k_tok, k_pick = jax.random.split(key)
    rnd = jax.random.randint(k_tok, (batch, seq), 0, V, jnp.int32)
    pick = jax.random.uniform(k_pick, (batch, seq)) < share

    def step(t, toks):
        nxt = jnp.argmax(logits(cfg, params, toks, None)[:, t], -1)
        col = jnp.where(pick[:, t + 1], nxt.astype(jnp.int32), rnd[:, t + 1])
        return toks.at[:, t + 1].set(col)

    return jax.lax.fori_loop(0, seq - 1, step, rnd)
