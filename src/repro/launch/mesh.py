"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the leading ``pod``
axis is pure data parallelism whose gradient all-reduce rides DCN — the
axis generalizes to any pod count (1000+ node posture: grow ``pod``).

Defined as functions (never module-level constants) so importing this
module does not touch jax device state; the dry-run forces 512 host
devices *before* any jax import (see dryrun.py).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

DATA_AXIS = 16
MODEL_AXIS = 16


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, DATA_AXIS, MODEL_AXIS) if multi_pod else (DATA_AXIS, MODEL_AXIS)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the layers place activations
    through ``with_sharding_constraint`` hints (``launch/hints.py``),
    which JAX accepts only on Auto axes (its default is now Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_axis: int | None = None):
    """Mesh over whatever devices exist (serving, tests, examples)."""
    n = len(jax.devices())
    m = model_axis or (2 if n % 2 == 0 and n > 1 else 1)
    return _auto_mesh((n // m, m), ("data", "model"))


def fsdp_axes(mesh) -> tuple:
    """The axes params/optimizer state are ZeRO-3 sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def batch_axes(mesh) -> tuple:
    return fsdp_axes(mesh)
