"""Production mesh construction.

``make_production_mesh`` is a *function* (not a module-level constant) so that
importing this module never touches jax device state.  The single-pod mesh is
16x16 = 256 chips (one v5e pod, all-ICI); the multi-pod mesh adds a leading
"pod" axis over DCN: 2 x 16 x 16 = 512 chips.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from ..core.compat import make_mesh
from ..core.pcontext import ParallelCtx, single_pod_ctx, multi_pod_ctx


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape: Tuple[int, ...] = (2, 4),
                   axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh for multi-host-device tests."""
    return make_mesh(shape, axes)


def make_ctx(mesh, *, ar_strategy: str = "flat",
             cross_pod_tp: bool = False,
             batch_replicated: bool = False, **kw) -> ParallelCtx:
    """Wire a ParallelCtx for one of the production meshes."""
    multi = "pod" in mesh.axis_names
    ctx = (multi_pod_ctx(ar_strategy=ar_strategy, cross_pod_tp=cross_pod_tp,
                         **kw)
           if multi else single_pod_ctx(ar_strategy=ar_strategy, **kw))
    if batch_replicated:  # long_500k: batch=1 cannot shard over dp
        ctx = ctx.replace(dp=(), fsdp=())
    return ctx


def tp_size(mesh, ctx: ParallelCtx) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in ctx.tp_slow + ctx.tp_fast:
        n *= sizes[a]
    return n


__all__ = ["make_production_mesh", "make_test_mesh", "make_ctx", "tp_size"]
