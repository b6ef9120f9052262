"""Plain reference of the hybrid Mamba-2 / attention decoder the search
compresses (Granite-4.0-H, HF ``GraniteMoeHybrid`` without experts):
token embedding times ``embedding_multiplier``; per layer RMSNorm ->
mixer -> residual add of the mixer's output times
``residual_multiplier`` -> RMSNorm -> SwiGLU FFN -> the same residual
add; final RMSNorm; a head tied to the embedding table, its logits
divided by ``logits_scaling``.

The mixer is either GQA attention without positional encoding, softmax
scale ``attention_multiplier``, or a Mamba-2 mixer (arXiv:2405.21060,
HF ``Mamba2Mixer``, one group): in_proj -> [z, xBC, dt]; depthwise
causal conv over xBC with its bias, then SiLU; split into x, B, C;
dt = softplus(dt + dt_bias), A = -exp(A_log); the state-space recurrence
run step by step, h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t,
y_t = C_t . h_t + D x_t; gated RMSNorm norm(y * silu(z)) over d_inner;
out_proj.

A compression policy enters as per-unit arrays in ``unit_names`` order.
``embed`` and ``head`` quantize their weight table. An attention layer's
``attn_qkv`` quantizes its input and the q/k/v weights and keeps its
``keep`` heads with the largest l1 norm of their query columns;
``attn_out`` quantizes the output projection. A Mamba-2 layer's
``ssm_in`` quantizes its input and in_proj and keeps the ``keep`` heads
with the largest l1 norm of their x-columns of in_proj (the pruned
heads' y is zeroed before the gated norm); ``ssm_out`` quantizes
out_proj and its input. Every layer's ``mlp_up`` quantizes its input
and the up and gate projections and keeps the ``keep`` channels with
the largest summed l1 norm of their up and gate columns; ``mlp_down``
quantizes the down projection.

Also here: the seeded weights the benchmark serves to the program, and
the seeded validation tokens, generated by the recurrent form.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.compression import fake_quant, keep_mask
from chipbench.reference.numerics import Numerics

NEG_INF = -1e30
KINDS = {"mamba": "ssm", "attention": "attn"}
MIXER_UNITS = {"ssm": ("ssm_in", "ssm_out"), "attn": ("attn_qkv",
                                                      "attn_out")}


def arch(cfg: dict) -> dict:
    """Shape facts of a configuration file, under short names. The
    mixer's width is ``mamba_expand`` x the hidden size, as HF's mixer
    sets it; at the published widths that is ``mamba_n_heads`` x
    ``mamba_d_head``."""
    if cfg["mamba_n_groups"] != 1:
        raise ValueError("one B/C group only")
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    d_inner = cfg["mamba_expand"] * d
    return {"d": d, "ff": cfg["shared_intermediate_size"],
            "layers": layers,
            "kinds": [KINDS[t] for t in cfg["layer_types"][:layers]],
            "heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"],
            "hd": d // cfg["num_attention_heads"],
            "vocab": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
            "d_inner": d_inner, "ssm_hd": cfg["mamba_d_head"],
            "ssm_heads": d_inner // cfg["mamba_d_head"],
            "d_state": cfg["mamba_d_state"], "conv": cfg["mamba_d_conv"],
            "conv_dim": d_inner + 2 * cfg["mamba_d_state"],
            "chunk": cfg["mamba_chunk_size"],
            "emb_mult": float(cfg["embedding_multiplier"]),
            "res_mult": float(cfg["residual_multiplier"]),
            "logits_scaling": float(cfg["logits_scaling"]),
            "attn_mult": float(cfg["attention_multiplier"])}


def unit_names(cfg: dict) -> list:
    """The compressible units in the order the policy arrays use."""
    names = ["embed"]
    for i, kind in enumerate(arch(cfg)["kinds"]):
        names += [f"L{i}.{u}" for u in MIXER_UNITS[kind]]
        names += [f"L{i}.mlp_up", f"L{i}.mlp_down"]
    return names + ["head"]


def make_params(cfg: dict, key):
    """Seeded float32 weights in the layout the program consumes (a list
    of per-layer dicts), shaped by the configuration's ``weights`` entry
    as ``reference/lm.py`` shapes Qwen2's:

    * normal weights scaled by 1/sqrt(fan-in), norm scales near 1; the
      residual branches' output projections (attention out, Mamba-2
      out_proj, FFN down) further scaled by ``branch_out_scale``, so
      that the residual stream holds the token's embedding in the share
      Qwen2's shaping gives it, after the embedding and residual
      multipliers;
    * embedding rows whose norms spread log-uniformly over
      ``embedding_row_norms``, so greedy margins range from confident
      to borderline;
    * channel 0 a massive activation of the head's input
      (``massive_final_norm_scale``) that the tied head does not read
      (embedding column 0 is zero): float logits are unchanged by it,
      per-token int8 ones are not;
    * Mamba-2: A uniform over ``ssm_A_range``; dt log-uniform over
      ``ssm_dt_range``, stored as its inverse softplus in dt_bias; D 1;
      conv weights normal over sqrt(width), small normal conv bias.
    """
    a = arch(cfg)
    d, ff, H, KV, hd, V = (a["d"], a["ff"], a["heads"], a["kv"], a["hd"],
                           a["vocab"])
    di, nh, cd, K = a["d_inner"], a["ssm_heads"], a["conv_dim"], a["conv"]
    wc = cfg["weights"]
    g_out = wc["branch_out_scale"]
    ks = iter(jax.random.split(key, 4 + 12 * a["layers"]))

    def w(shape, fan, gain=1.0):
        return jax.random.normal(next(ks), shape, jnp.float32) * (
            gain / math.sqrt(fan))

    def scale(shape):
        return 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)

    def ffn():
        return {"mlp_norm": {"scale": scale((d,))},
                "mlp": {"w_up": {"w": w((d, ff), d)},
                        "w_gate": {"w": w((d, ff), d)},
                        "w_down": {"w": w((ff, d), ff, g_out)}}}

    blocks = []
    for kind in a["kinds"]:
        if kind == "attn":
            blk = {"attn_norm": {"scale": scale((d,))},
                   "attn": {"wq": {"w": w((d, H * hd), d)},
                            "wk": {"w": w((d, KV * hd), d)},
                            "wv": {"w": w((d, KV * hd), d)},
                            "wo": {"w": w((H * hd, d), H * hd, g_out)}}}
        else:
            a_lo, a_hi = wc["ssm_A_range"]
            t_lo, t_hi = wc["ssm_dt_range"]
            dt = t_lo * (t_hi / t_lo) ** jax.random.uniform(next(ks), (nh,))
            blk = {"norm": {"scale": scale((d,))},
                   "ssm": {"in_proj": w((d, 2 * di + 2 * a["d_state"] + nh),
                                        d),
                           "out_proj": w((di, d), di, g_out),
                           "conv_w": w((K, cd), K),
                           "conv_b": 0.02 * jax.random.normal(
                               next(ks), (cd,), jnp.float32),
                           "A_log": jnp.log(jax.random.uniform(
                               next(ks), (nh,), jnp.float32, a_lo, a_hi)),
                           "D": jnp.ones((nh,), jnp.float32),
                           "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                           "norm_scale": scale((di,))}}
        blk.update(ffn())
        blocks.append(blk)
    lo, hi = wc["embedding_row_norms"]
    norms = lo * (hi / lo) ** jax.random.uniform(next(ks), (V, 1))
    embed = (w((V, d), d) * norms).at[:, 0].set(0.0)
    return {"embed": embed, "blocks": blocks,
            "final_norm": {"scale": scale((d,)).at[0].set(
                wc["massive_final_norm_scale"])}}


def policy_arrays(cfg: dict, keep, wb, ab) -> dict:
    """(units,) policy arrays in ``unit_names`` order -> the per-layer
    tuples ``logits`` takes: (mixer in (keep, w, a), mixer out (w, a),
    up (keep, w, a), down (w, a))."""
    keep, wb, ab = (jnp.asarray(x) for x in (keep, wb, ab))
    layers = []
    for i in range(arch(cfg)["layers"]):
        j = 1 + 4 * i
        layers.append(((keep[j], wb[j], ab[j]), (wb[j + 1], ab[j + 1]),
                       (keep[j + 2], wb[j + 2], ab[j + 2]),
                       (wb[j + 3], ab[j + 3])))
    return {"embed": wb[0], "head": wb[-1], "layers": layers}


def _rms(x, scale, eps, num):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return num.cast(y * scale)


def _proj(x, w, wbits, abits, num, q=True):
    if q:
        x = fake_quant(x, abits, num)
        w = fake_quant(w, wbits, num)
    return num.mm("bsi,io->bso", num.cast(x), num.cast(w))


def _attention(a, p, h, pol, num, q_on):
    (qk, qw, qa), (ow, oa) = pol
    B, S, _ = h.shape
    H, KV, hd = a["heads"], a["kv"], a["hd"]
    q = _proj(h, p["wq"]["w"], qw, qa, num, q_on).reshape(B, S, KV,
                                                         H // KV, hd)
    k = _proj(h, p["wk"]["w"], qw, qa, num, q_on).reshape(B, S, KV, hd)
    v = _proj(h, p["wv"]["w"], qw, qa, num, q_on).reshape(B, S, KV, hd)
    s = num.mm("bqkgd,blkd->bkgql", q, k).astype(jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s * a["attn_mult"], NEG_INF)
    pr = jax.nn.softmax(s, -1)
    o = num.mm("bkgql,blkd->bqkgd", num.cast(pr), v).reshape(B, S, H, hd)
    hscore = jnp.sum(jnp.abs(p["wq"]["w"]).reshape(-1, H, hd), (0, 2))
    o = o * num.cast(keep_mask(hscore, qk))[None, None, :, None]
    return _proj(o.reshape(B, S, H * hd), p["wo"]["w"], ow, oa, num, q_on)


def ssd_sequential(xdt, dA, Bm, Cm, num, h0=None):
    """The state-space recurrence step by step: xdt [b,s,h,p] (inputs
    times dt), dA [b,s,h] (dt A), Bm, Cm [b,s,n]. Returns y [b,s,h,p]
    (without the D skip) and the last state [b,h,p,n]."""
    b, _, h, p = xdt.shape
    if h0 is None:
        h0 = jnp.zeros((b, h, p, Bm.shape[-1]), num.dtype)

    def step(st, inp):
        x_t, a_t, b_t, c_t = inp
        st = num.cast(jnp.exp(a_t)[..., None, None] * st
                      + x_t[..., None] * b_t[:, None, None, :])
        return st, num.mm("bn,bhpn->bhp", c_t, st)

    swap = lambda t: jnp.swapaxes(num.cast(t), 0, 1)
    last, ys = jax.lax.scan(step, num.cast(h0),
                            (swap(xdt), swap(dA), swap(Bm), swap(Cm)))
    return jnp.swapaxes(ys, 0, 1), last


def _mamba_pre(a, p, xbc_conv, dt_raw, num):
    """SiLU'd conv output and raw dt -> (x heads, dt x, dt A, B, C)."""
    xbc = jax.nn.silu(xbc_conv.astype(jnp.float32))
    di, n = a["d_inner"], a["d_state"]
    x, Bm, Cm = xbc[..., :di], xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    xh = x.reshape(*x.shape[:-1], a["ssm_heads"], a["ssm_hd"])
    dA = dt * -jnp.exp(p["A_log"])
    return (num.cast(xh), num.cast(xh * dt[..., None]), num.cast(dA),
            num.cast(Bm), num.cast(Cm))


def _mamba_post(a, p, y, z, keep_heads, pol_out, num, q_on):
    """Head pruning, gated RMSNorm over d_inner, out_proj."""
    ow, oa = pol_out
    score = jnp.sum(jnp.abs(p["in_proj"][:, a["d_inner"]:2 * a["d_inner"]])
                    .reshape(-1, a["ssm_heads"], a["ssm_hd"]), (0, 2))
    y = y * num.cast(keep_mask(score, keep_heads))[..., :, None]
    y = y.reshape(*y.shape[:-2], a["d_inner"])
    g = num.cast(y.astype(jnp.float32)
                 * jax.nn.silu(z.astype(jnp.float32)))
    g = _rms(g, p["norm_scale"], a["eps"], num)
    return _proj(g, p["out_proj"], ow, oa, num, q_on)


def _mamba(a, p, h, pol, num, q_on):
    (ik, iw, ia), pol_out = pol
    S, K, di, cd = h.shape[1], a["conv"], a["d_inner"], a["conv_dim"]
    proj = _proj(h, p["in_proj"], iw, ia, num, q_on)
    z, xbc, dt = proj[..., :di], proj[..., di:di + cd], proj[..., di + cd:]
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + S] * num.cast(p["conv_w"][i]) for i in range(K))
    xh, xdt, dA, Bm, Cm = _mamba_pre(a, p, conv + num.cast(p["conv_b"]), dt,
                                     num)
    y, _ = ssd_sequential(xdt, dA, Bm, Cm, num)
    y = y + num.cast(p["D"])[:, None] * xh
    return _mamba_post(a, p, y, z, ik, pol_out, num, q_on)


def _ffn(a, p, x, pol, num, q_on):
    (uk, uw, ua), (dw, da) = pol
    m = p["mlp"]
    h = _rms(x, p["mlp_norm"]["scale"], a["eps"], num)
    up = _proj(h, m["w_up"]["w"], uw, ua, num, q_on)
    gate = _proj(h, m["w_gate"]["w"], uw, ua, num, q_on)
    fscore = (jnp.sum(jnp.abs(m["w_up"]["w"]), 0)
              + jnp.sum(jnp.abs(m["w_gate"]["w"]), 0))
    act = num.cast(jax.nn.silu(gate.astype(jnp.float32))
                   * up.astype(jnp.float32)) \
        * num.cast(keep_mask(fscore, uk))
    return _proj(act, m["w_down"]["w"], dw, da, num, q_on)


def _uncompressed(a) -> dict:
    full = lambda n: jnp.asarray(n, jnp.int32)
    f32 = (full(32), full(32))
    layers = []
    for kind in a["kinds"]:
        keep = a["heads"] if kind == "attn" else a["ssm_heads"]
        layers.append(((full(keep),) + f32, f32, (full(a["ff"]),) + f32,
                       f32))
    return {"layers": layers}


def _embed(a, params, tokens, pol, num):
    table = params["embed"]
    if pol is not None:
        table = fake_quant(table, pol["embed"], num)
    return num.cast(jnp.take(table, tokens, axis=0) * a["emb_mult"])


def _head(a, params, x, pol, num):
    x = _rms(x, params["final_norm"]["scale"], a["eps"], num)
    head = params["embed"].T
    if pol is not None:
        head = fake_quant(head, pol["head"], num)
    lg = num.mm("bsd,dv->bsv", x, num.cast(head)).astype(jnp.float32)
    return lg / a["logits_scaling"]


def logits(cfg: dict, params, tokens, pol, prec: str = "f32"):
    """[B, S, vocab] float32 logits of ``tokens`` under the policy arrays
    ``pol`` (``policy_arrays``; None = uncompressed)."""
    with jax.default_matmul_precision("highest"):
        num = Numerics(prec)
        a = arch(cfg)
        q_on = pol is not None
        lp = pol if q_on else _uncompressed(a)
        x = _embed(a, params, tokens, pol, num)
        res = lambda x, o: num.cast(x + o * a["res_mult"])
        for kind, p, lpol in zip(a["kinds"], params["blocks"],
                                 lp["layers"]):
            if kind == "attn":
                h = _rms(x, p["attn_norm"]["scale"], a["eps"], num)
                x = res(x, _attention(a, p["attn"], h, lpol[:2], num, q_on))
            else:
                h = _rms(x, p["norm"]["scale"], a["eps"], num)
                x = res(x, _mamba(a, p["ssm"], h, lpol[:2], num, q_on))
            x = res(x, _ffn(a, p, x, lpol[2:], num, q_on))
        return _head(a, params, x, pol, num)


def accuracy(cfg: dict, params, tokens, pol, prec: str = "f32"):
    """Next-token top-1 accuracy of the compressed model."""
    lg = logits(cfg, params, tokens, pol, prec)[:, :-1]
    return jnp.mean((jnp.argmax(lg, -1) == tokens[:, 1:]).astype(jnp.float32))


# ---------------------------------------------------------------------------
# The recurrent form: one token at a time against each layer's state
# ---------------------------------------------------------------------------

def init_state(cfg: dict, batch: int, max_len: int) -> list:
    """Per layer: the Mamba-2 mixer's last conv inputs and recurrent
    state, or the attention layer's keys and values."""
    a = arch(cfg)
    out = []
    for kind in a["kinds"]:
        if kind == "attn":
            kv = jnp.zeros((batch, max_len, a["kv"], a["hd"]), jnp.float32)
            out.append({"k": kv, "v": kv})
        else:
            out.append({"conv": jnp.zeros((batch, a["conv"] - 1,
                                           a["conv_dim"]), jnp.float32),
                        "h": jnp.zeros((batch, a["ssm_heads"], a["ssm_hd"],
                                        a["d_state"]), jnp.float32)})
    return out


def step_logits(cfg: dict, params, token, pos, state):
    """float32 logits [B, vocab] of the uncompressed model at position
    ``pos`` for ``token`` [B], and the state after it."""
    with jax.default_matmul_precision("highest"):
        num = Numerics("f32")
        a = arch(cfg)
        lp = _uncompressed(a)["layers"]
        x = _embed(a, params, token[:, None], None, num)
        res = lambda x, o: x + o * a["res_mult"]
        new = []
        for kind, p, st, lpol in zip(a["kinds"], params["blocks"], state,
                                     lp):
            if kind == "attn":
                h = _rms(x, p["attn_norm"]["scale"], a["eps"], num)
                o, st = _attention_step(a, p["attn"], h, pos, st, num)
            else:
                h = _rms(x, p["norm"]["scale"], a["eps"], num)
                o, st = _mamba_step(a, p["ssm"], h, lpol[:2], st, num)
            new.append(st)
            x = res(x, o)
            x = res(x, _ffn(a, p, x, lpol[2:], num, False))
        return _head(a, params, x, None, num)[:, 0], new


def _attention_step(a, p, h, pos, st, num):
    H, KV, hd = a["heads"], a["kv"], a["hd"]
    B = h.shape[0]
    q = num.mm("bsi,io->bso", h, p["wq"]["w"]).reshape(B, KV, H // KV, hd)
    k = num.mm("bsi,io->bso", h, p["wk"]["w"]).reshape(B, 1, KV, hd)
    v = num.mm("bsi,io->bso", h, p["wv"]["w"]).reshape(B, 1, KV, hd)
    st = {"k": jax.lax.dynamic_update_slice(st["k"], k, (0, pos, 0, 0)),
          "v": jax.lax.dynamic_update_slice(st["v"], v, (0, pos, 0, 0))}
    s = num.mm("bkgd,blkd->bkgl", q, st["k"]) * a["attn_mult"]
    s = jnp.where(jnp.arange(s.shape[-1]) <= pos, s, NEG_INF)
    o = num.mm("bkgl,blkd->bkgd", jax.nn.softmax(s, -1), st["v"])
    return num.mm("bsi,io->bso", o.reshape(B, 1, H * hd), p["wo"]["w"]), st


def _mamba_step(a, p, h, pol, st, num):
    di, cd = a["d_inner"], a["conv_dim"]
    proj = num.mm("bsi,io->bso", h, p["in_proj"])[:, 0]
    z, xbc, dt = proj[..., :di], proj[..., di:di + cd], proj[..., di + cd:]
    win = jnp.concatenate([st["conv"], xbc[:, None]], 1)      # [B, K, C]
    conv = jnp.einsum("bkc,kc->bc", win, p["conv_w"]) + p["conv_b"]
    xh, xdt, dA, Bm, Cm = _mamba_pre(a, p, conv, dt, num)
    hs = (jnp.exp(dA)[..., None, None] * st["h"]
          + xdt[..., None] * Bm[:, None, None, :])
    y = num.mm("bn,bhpn->bhp", Cm, hs) + p["D"][:, None] * xh
    out = _mamba_post(a, p, y[:, None], z[:, None], a["ssm_heads"], pol[1],
                      num, False)
    return out, {"conv": win[:, 1:], "h": hs}


def greedy_tokens(cfg: dict, params, key, seq: int, greedy_share):
    """Seeded validation tokens, one row per entry of ``greedy_share``:
    the first token and a random ``1 - share`` of the rest of a row are
    uniform draws; every other next token is the uncompressed reference
    model's greedy choice, generated one position at a time by the
    recurrent form (``step_logits``), which gives the full forward's
    logits up to rounding."""
    V = arch(cfg)["vocab"]
    share = jnp.asarray(greedy_share, jnp.float32)[:, None]
    batch = share.shape[0]
    k_tok, k_pick = jax.random.split(key)
    rnd = jax.random.randint(k_tok, (batch, seq), 0, V, jnp.int32)
    pick = jax.random.uniform(k_pick, (batch, seq)) < share

    def step(t, carry):
        toks, st = carry
        lg, st = step_logits(cfg, params, toks[:, t], t, st)
        nxt = jnp.argmax(lg, -1).astype(jnp.int32)
        col = jnp.where(pick[:, t + 1], nxt, rnd[:, t + 1])
        return toks.at[:, t + 1].set(col), st

    toks, _ = jax.lax.fori_loop(0, seq - 1, step,
                                (rnd, init_state(cfg, batch, seq)))
    return toks
