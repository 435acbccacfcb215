"""Mesh construction for the installed JAX.

``jax.make_mesh`` makes every axis ``Explicit`` unless told otherwise; the
whole tree is written for ``Auto`` axes (sharding propagated by the
compiler, collectives spelled out inside ``jax.shard_map``), so meshes are
built through this one alias:

    from repro.core.compat import make_mesh
    mesh = make_mesh((2, 4), ("pod", "model"))
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


__all__ = ["make_mesh"]
