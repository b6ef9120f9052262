"""Per-assigned-architecture smoke tests (deliverable f): reduced config,
one forward + one train step on CPU, asserting shapes and finiteness."""
import jax
import jax.numpy as jnp
import pytest

from repro.models import model as M
from repro.models.registry import ARCH_IDS, get_config
from repro.optim.optimizer import OptimizerConfig, adamw_init
from repro.train.train_step import make_train_step


def _batch(cfg, B=2, S=32):
    out = {}
    if cfg.frontend == "audio_stub":
        out["embeds"] = jnp.ones((B, S, cfg.d_model), jnp.float32)
        out["labels"] = jnp.zeros((B, S), jnp.int32)
    else:
        out["tokens"] = jax.random.randint(jax.random.PRNGKey(0), (B, S), 0,
                                           cfg.vocab_size)
        if cfg.frontend == "vision_stub":
            out["embeds"] = jnp.ones((B, cfg.frontend_len, cfg.d_model),
                                     jnp.float32)
    return out


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_smoke(arch):
    cfg = get_config(arch, smoke=True).replace(param_dtype="float32",
                                               compute_dtype="float32")
    params = M.init(cfg, jax.random.PRNGKey(0))
    b = _batch(cfg)
    logits = M.forward(cfg, params, tokens=b.get("tokens"),
                       embeds=b.get("embeds"))
    B = 2
    S = 32
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_smoke(arch):
    cfg = get_config(arch, smoke=True).replace(param_dtype="float32",
                                               compute_dtype="float32")
    params = M.init(cfg, jax.random.PRNGKey(0))
    opt_cfg = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    opt = adamw_init(params, opt_cfg)
    step = jax.jit(make_train_step(cfg, opt_cfg))
    b = _batch(cfg)
    params2, opt2, metrics = step(params, opt, b)
    assert bool(jnp.isfinite(metrics["loss"]))
    # parameters actually moved
    moved = jax.tree.map(lambda a, c: float(jnp.max(jnp.abs(a - c))),
                         params, params2)
    assert max(jax.tree.leaves(moved)) > 0


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mixtral-8x22b",
                                  "mamba2-780m", "recurrentgemma-2b",
                                  "granite-4.0-h-micro"])
def test_decode_matches_prefill(arch):
    cfg = get_config(arch, smoke=True).replace(param_dtype="float32",
                                               compute_dtype="float32")
    params = M.init(cfg, jax.random.PRNGKey(1))
    B, S = 2, 10
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                              cfg.vocab_size)
    full = M.forward(cfg, params, tokens=toks)
    cache = M.init_cache(cfg, B, S, dtype=jnp.float32)
    outs = []
    for t in range(S):
        lg, cache = M.decode_step(cfg, params, cache, toks[:, t:t + 1], t)
        outs.append(lg)
    dec = jnp.concatenate(outs, 1)
    rel = float(jnp.max(jnp.abs(full - dec)) / (jnp.max(jnp.abs(full))
                                                + 1e-9))
    assert rel < 1e-4


def test_encoder_has_no_decode():
    from repro.configs.base import SHAPES_BY_NAME, cell_supported
    cfg = get_config("hubert-xlarge")
    ok, reason = cell_supported(cfg, SHAPES_BY_NAME["decode_32k"])
    assert not ok and "encoder" in reason


def test_long_context_skips():
    from repro.configs.base import SHAPES_BY_NAME, cell_supported
    long = SHAPES_BY_NAME["long_500k"]
    assert not cell_supported(get_config("olmo-1b"), long)[0]
    assert cell_supported(get_config("mamba2-780m"), long)[0]
    assert cell_supported(get_config("mixtral-8x22b"), long)[0]   # SWA
    assert cell_supported(get_config("recurrentgemma-2b"), long)[0]
