"""Production meshes (TPU v5e). A FUNCTION, not a module constant — importing
this module never touches jax device state."""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``.

    JAX 0.9 makes mesh axes ``Explicit`` by default, which puts shardings
    into array types: the vmapped local step then rejects params sharded
    over ``data`` next to inputs that are not. ``Auto`` axes leave the
    placement to ``in_shardings``/``shard_map`` as the train steps expect."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ("data", "model").
    Multi-pod: 2 pods x 256 = 512 chips ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Dev/test mesh over whatever devices exist (CPU: usually 1)."""
    n = len(jax.devices())
    return make_mesh((n // model, model), ("data", "model"))


def batch_axes(mesh) -> Tuple[str, ...]:
    """Axes that shard the batch (data parallel, pod-extended)."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


# Hardware constants for the roofline (TPU v5e)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
