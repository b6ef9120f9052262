"""Shared benchmark substrate: the two Galen search testbeds.

* LM testbed  — 4L/128d transformer trained on the Zipfian-bigram language
  (the LM-serving analogue of the paper's ResNet18/CIFAR-10: small enough
  to train on one CPU core in ~2 min, accuracy degrades measurably under
  compression).
* ResNet testbed — the paper's own model family on blob images.

Trained weights are cached under artifacts/ so every benchmark and test
reuses one training run. The latency-oracle context is the batch-1 decode
scenario (single-stream serving — the embedded-device analogue).
``lm_search`` and ``resnet_search`` build the search engine on them with
the paper-table settings.
"""
from __future__ import annotations

import os
import pickle

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.compress import CompressibleLM, CompressibleResNet
from repro.core.ddpg import DDPGConfig
from repro.core.latency import LatencyContext
from repro.core.reward import RewardConfig
from repro.core.search import CompressionSearch, SearchConfig
from repro.core.sensitivity import SensitivityResult, run_sensitivity
from repro.models.resnet import ResNetConfig

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")

# d_model=256 keeps every unit 256-aligned so the MIX (int4) option is
# hardware-legal everywhere — the full paper action space is reachable.
LM_CFG = ArchConfig(name="testbed-lm", num_layers=4, d_model=256,
                    num_heads=8, num_kv_heads=4, head_dim=32, d_ff=1024,
                    vocab_size=256, scan_layers=True)

RESNET_CFG = ResNetConfig(name="testbed-resnet", stages=(2, 2, 2),
                          widths=(16, 32, 64), num_classes=10, img_size=16)

# single-stream serving on one v5e chip — the "Raspberry Pi" of this repo
SERVE_CTX = LatencyContext(tokens=1, seq_ctx=512, mode="decode", batch=1)
# image-classification context for the ResNet testbed (per-image latency)
IMG_CTX = LatencyContext(tokens=1, seq_ctx=0, mode="prefill", batch=1)

FULL = os.environ.get("GALEN_BENCH_FULL", "0") == "1"

# paper: 310 (quant) / 410 (prune, joint) episodes, 10 warm-up.
EPISODES = {"p": 410, "q": 310, "pq": 410} if FULL else \
    {"p": 60, "q": 50, "pq": 60}
WARMUP = 10
UPDATES = 48 if FULL else 24

_sens_cache = {}


def _cache(path, builder):
    os.makedirs(ART, exist_ok=True)
    f = os.path.join(ART, path)
    if os.path.exists(f):
        with open(f, "rb") as fh:
            return pickle.load(fh)
    obj = builder()
    with open(f, "wb") as fh:
        pickle.dump(obj, fh)
    return obj


def get_lm_testbed(steps: int = 220):
    """Returns (cfg, params, val_batch, clean_accuracy)."""

    def build():
        from repro.train.trainer import train_testbed_lm
        params, val, acc = train_testbed_lm(LM_CFG, steps=steps, batch=16,
                                            seq=48)
        return {"params": jax.device_get(params),
                "val": jax.device_get(val), "acc": acc}

    d = _cache("testbed_lm.pkl", build)
    params = jax.tree.map(jnp.asarray, d["params"])
    val = jax.tree.map(jnp.asarray, d["val"])
    return LM_CFG, params, val, d["acc"]


def get_resnet_testbed(steps: int = 200):
    def build():
        from repro.train.trainer import train_testbed_resnet
        params, val, acc = train_testbed_resnet(RESNET_CFG, steps=steps,
                                                batch=64)
        return {"params": jax.device_get(params),
                "val": jax.device_get(val), "acc": acc}

    d = _cache("testbed_resnet.pkl", build)
    params = jax.tree.map(jnp.asarray, d["params"])
    val = jax.tree.map(jnp.asarray, d["val"])
    return RESNET_CFG, params, val, d["acc"]


def lm_search(methods: str, c: float, seed: int = 0, episodes=None,
              sens_enabled: bool = True,
              action_dim: int = 0) -> CompressionSearch:
    """The search engine on the LM testbed. ``action_dim`` > the
    method's native count pads the agent's action space (required for
    mixed-method PopulationSearch members)."""
    cfg, params, val, acc = get_lm_testbed()
    # smaller eval batch: ~2x faster episodes, ±2% accuracy noise (the
    # paper also validates on a small split during search)
    val = {k: v[:32] for k, v in val.items()}
    cm = CompressibleLM(cfg, params)
    key = ("lm", sens_enabled)
    if key not in _sens_cache:
        # disabled: an empty table gives constant sensitivity features
        _sens_cache[key] = run_sensitivity(cm, val) if sens_enabled \
            else SensitivityResult({s.name: {} for s in cm.specs})
    scfg = SearchConfig(
        methods=methods,
        episodes=episodes or EPISODES[methods],
        reward=RewardConfig(target_ratio=c, beta=-3.0),
        ddpg=DDPGConfig(warmup_episodes=WARMUP, updates_per_episode=UPDATES,
                        batch_size=128, buffer_size=2000,
                        action_dim=action_dim or 1),
        seed=seed)
    return CompressionSearch(cm, val, scfg, SERVE_CTX, sens=_sens_cache[key])


def resnet_search(methods: str, c: float, seed: int = 0,
                  episodes=None) -> CompressionSearch:
    """The search engine on the ResNet testbed."""
    rcfg, params, val, acc = get_resnet_testbed()
    cm = CompressibleResNet(rcfg, params)
    if "resnet" not in _sens_cache:
        _sens_cache["resnet"] = run_sensitivity(cm, val)
    scfg = SearchConfig(
        methods=methods,
        episodes=episodes or EPISODES[methods],
        reward=RewardConfig(target_ratio=c, beta=-3.0),
        ddpg=DDPGConfig(warmup_episodes=WARMUP, updates_per_episode=UPDATES,
                        batch_size=128, buffer_size=2000),
        seed=seed)
    return CompressionSearch(cm, val, scfg, IMG_CTX,
                             sens=_sens_cache["resnet"])
