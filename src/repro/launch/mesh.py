"""Production mesh construction.

`make_production_mesh` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run driver
sets XLA_FLAGS for 512 host devices *before* any jax initialization.
"""
from __future__ import annotations

from ..configs.base import MeshConfig
from ..parallel.sharding import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def production_mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MeshConfig(data=16, model=16, pod=2 if multi_pod else 1)


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU-device-count tests (requires >= data*model devices)."""
    return auto_mesh((data, model), ("data", "model"))
