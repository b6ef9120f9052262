"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — required because the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.

Topology (TPU v5e): 16×16 chips per pod (256), ICI within a pod; the
``pod`` axis spans pods over DCN. Axes:
  data  — batch / FSDP shards (gradient + FSDP collectives)
  model — TP / EP shards (activation collectives)
  pod   — extra data parallelism across pods (gradient all-reduce on DCN,
          optionally compressed — optim/grad_compression.py)
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the sharding rules here place
    arrays with ``with_sharding_constraint`` / ``NamedSharding``, which
    ``Explicit`` axes (the default of ``jax.make_mesh`` in current JAX)
    reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_dev_mesh(data: int = 1, model: int = 1):
    """Small mesh for tests and fleets (requires >= data*model local devices).

    Raises a ``ValueError`` naming the required device count when the host
    has too few — ``jax.make_mesh`` otherwise fails with an opaque reshape
    error deep inside device assignment.
    """
    need = data * model
    have = len(jax.devices())
    if have < need:
        raise ValueError(
            f"make_dev_mesh(data={data}, model={model}) needs {need} local "
            f"device(s) but only {have} are visible. On CPU, launch a fresh "
            f"process with XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need} (must be set before jax initializes), or shrink the "
            "mesh — e.g. distributed.fault_tolerance.elastic_data_axis "
            "picks the largest data axis the surviving devices support.")
    return auto_mesh((data, model), ("data", "model"))
