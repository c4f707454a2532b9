"""Mesh construction: the one place a ``jax.sharding.Mesh`` is built.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax initialization).

Every mesh gets ``AxisType.Auto`` axes.  ``jax.make_mesh`` defaults to
``Explicit`` axes, under which sharding-in-types rejects ops whose output
sharding the compiler would otherwise infer (the embedding gather, the
sample sort's all-to-all); this repo places arrays with ``NamedSharding``
and lets GSPMD propagate, which is what ``Auto`` means.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with ``Auto`` axis types (see module doc)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_dev_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh for tests/examples on however many devices exist."""
    return make_mesh((n_data, n_model), ("data", "model"))
