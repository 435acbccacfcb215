"""Mesh-topology helpers: one place that turns a requested layout
(tp ways, pod split, AR knobs) into a ``(mesh, ctx, tp)`` triple, plus
the device carving that gives every serving replica its own disjoint
mesh.

Historically each driver (``launch.serve``, dist cases, benchmarks)
built its mesh inline; the multi-replica router needs the same
construction *parameterized by an explicit device subset* so N replicas
can coexist in one process without sharing collectives.  ``jax.devices()``
is carved into contiguous groups (replica i gets devices
``[i*tp, (i+1)*tp)``) — contiguous so a replica's fast axis stays on
neighbouring devices, matching how dp replicas are placed on real
fabrics (paper Sec. 3.1's topology hierarchy).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax

from ..core.pcontext import ParallelCtx, LOCAL


def mesh_and_ctx(tp: int, pods: int = 1, *, ar_strategy: str = "flat",
                 overlap: bool = False, seq_parallel: str = "off",
                 ar_quant: str = "none",
                 devices: Optional[Sequence] = None
                 ) -> Tuple[object, ParallelCtx, int]:
    """(mesh, ctx, tp_total) for the requested layout; local when tp == 1.

    ``devices`` restricts the mesh to an explicit device subset (must hold
    exactly ``tp`` devices) — the per-replica construction path.  With
    ``tp == 1`` the mesh is None and every collective is the identity, so
    a 1-way "replica" is just the local engine path.
    """
    ctx = LOCAL.replace(ar_strategy=ar_strategy, overlap_matmul=overlap,
                        seq_parallel=seq_parallel, ar_quant=ar_quant)
    if tp <= 1:
        return None, ctx, 1
    if tp % pods:
        raise ValueError(f"tp={tp} not divisible by pods={pods}")
    if devices is not None and len(devices) != tp:
        raise ValueError(f"device subset holds {len(devices)} devices, "
                         f"need exactly tp={tp}")
    from ..core.compat import make_mesh
    if pods > 1:
        mesh = make_mesh((pods, tp // pods), ("pod", "model"), devices=devices)
        ctx = ctx.replace(tp_fast=("model",), tp_slow=("pod",),
                          ep=("model",))
    else:
        mesh = make_mesh((tp,), ("model",),
                         devices=devices)
        ctx = ctx.replace(tp_fast=("model",), ep=("model",))
    return mesh, ctx, tp


def replica_device_groups(n_replicas: int, tp: int,
                          devices: Optional[Sequence] = None) -> list:
    """Carve the device pool into ``n_replicas`` disjoint contiguous
    groups of ``tp`` devices each (replica i owns ``[i*tp, (i+1)*tp)``).

    With ``tp == 1`` replica i runs the local (mesh-less) engine path on
    ``devices[i]`` alone.  A caller that means to stack several replicas
    on one device (a CPU test) passes that device repeated in ``devices``.
    """
    if n_replicas < 1:
        raise ValueError(f"need n_replicas >= 1, got {n_replicas}")
    tp = max(tp, 1)
    pool = list(jax.devices()) if devices is None else list(devices)
    need = n_replicas * tp
    if len(pool) < need:
        raise ValueError(
            f"{n_replicas} replicas x tp={tp} needs {need} devices, "
            f"but only {len(pool)} are visible")
    return [pool[i * tp:(i + 1) * tp] for i in range(n_replicas)]


__all__ = ["mesh_and_ctx", "replica_device_groups"]
