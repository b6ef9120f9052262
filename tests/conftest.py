import os
import sys

# Tests must see ONE device (the dry-run sets its own flags in a fresh
# process). Keep compilation light.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def require_devices(n: int):
    """Skip (with the forced-host-device recipe in the reason) when the
    current process has fewer than ``n`` local devices. Mesh-size-gated
    tests call this first: they skip in the ordinary 1-device suite and
    run in CI's dedicated multi-device step, which launches a fresh
    pytest process under ``XLA_FLAGS=--xla_force_host_platform_device_
    count=8`` (the flag only works before jax first initializes)."""
    have = len(jax.devices())
    if have < n:
        pytest.skip(
            f"needs {n} local devices, this process has {have}; run in a "
            f"fresh process under XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={n}")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def tiny_lm():
    """Small untrained LM + batch for mechanics tests (fast)."""
    import jax.numpy as jnp
    from repro.configs.base import ArchConfig
    from repro.core.compress import CompressibleLM
    from repro.data.pipeline import bigram_lm
    from repro.models import model as M

    cfg = ArchConfig(name="t", num_layers=3, d_model=64, num_heads=4,
                     num_kv_heads=2, head_dim=16, d_ff=256, vocab_size=128,
                     scan_layers=True)
    params = M.init(cfg, jax.random.PRNGKey(0))
    batch = bigram_lm(cfg.vocab_size, 8, 32, seed=3)
    return CompressibleLM(cfg, params), batch


@pytest.fixture(scope="session")
def tiny_resnet():
    from repro.core.compress import CompressibleResNet
    from repro.data.pipeline import blob_images
    from repro.models import resnet as R

    cfg = R.ResNetConfig(stages=(1, 1), widths=(8, 16), img_size=8,
                         num_classes=4)
    params = R.init(cfg, jax.random.PRNGKey(0))
    batch = blob_images(4, 16, 8, seed=5)
    return CompressibleResNet(cfg, params), batch


@pytest.fixture(autouse=True)
def _empty_span_record():
    """Each test starts with the program's span record empty: the record
    is process-wide, and a test file that runs a search would otherwise
    leave its spans to whichever file the same worker runs next."""
    from repro.core import spans
    spans.drain()
    yield
