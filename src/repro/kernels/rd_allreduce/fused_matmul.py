"""Collective matmul as a single Pallas TPU kernel: row-parallel GEMM with
the slow-axis recursive-doubling exchange fused into its epilogue.

This is the kernel form of :func:`repro.core.overlap.collective_matmul` for
the cross-pod (DCN-dominant) decode deployments the paper targets.  The
output features are split into ``n_chunks`` column blocks; the kernel

  1. computes the GEMM for block c,
  2. immediately starts the step-0 XOR-peer remote DMA for block c,
  3. computes the GEMM for block c+1 *while block c is on the wire*,

so the first (most expensive, full-payload) RD exchange step hides entirely
behind MXU work — the paper's Sec. 4.2.1 chunked non-blocking communication
applied at the producer rather than after it.  Remaining RD steps reuse the
double-buffered ``make_async_remote_copy`` machinery of ``_rd_kernel`` (see
``kernel.py``; same per-step barrier-semaphore handshake replacing the
paper's sequence numbers).

Layout contract (the ``ops``-style wrapper below handles it):
  x: (M, K)                       — local activation rows x contracted dim
  w: (n_chunks, K, chunk_d)       — column blocks of this device's weight
  out: (n_chunks, M, chunk_d)     — RD-reduced over the slow axis

Fast-axis (ICI) reduction is intentionally left to the caller: fusing it
would re-serialize the GEMM against the intra-pod phase, and on the slow-axis
crossings this kernel targets the ICI psum is noise (DESIGN.md
§Overlap-and-autotune).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel import enter_barrier


def _fused_kernel(x_ref, w_ref, out_ref, recv_ref, step_sem, send_sem,
                  recv_sem, *, axis_name: str, n_devices: int,
                  n_chunks: int):
    my = lax.axis_index(axis_name)
    n_steps = int(math.log2(n_devices))
    enter_barrier(axis_name, my, n_steps)

    # --- step 0, fused into the GEMM epilogue ------------------------------
    # Handshake once with the step-0 peer so its recv buffer is known-free
    # before any chunk lands (same race the per-step semaphores in
    # _rd_kernel prevent).
    peer0 = {axis_name: my ^ 1}   # MESH address: other coordinates kept
    pltpu.semaphore_signal(step_sem.at[0], 1, device_id=peer0,
                           device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(step_sem.at[0], 1)
    copies = []
    for c in range(n_chunks):
        acc = jnp.dot(x_ref[...], w_ref[c],
                      preferred_element_type=jnp.float32)
        out_ref[c] = acc.astype(out_ref.dtype)
        copy = pltpu.make_async_remote_copy(
            src_ref=out_ref.at[c],
            dst_ref=recv_ref.at[0, c],
            send_sem=send_sem.at[c],
            recv_sem=recv_sem.at[c],
            device_id=peer0,
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        copy.start()  # chunk c rides DCN while chunk c+1 runs on the MXU
        copies.append(copy)
    for c in range(n_chunks):
        copies[c].wait()
        out_ref[c] = out_ref[c] + recv_ref[0, c]

    # --- remaining RD steps (identical to _rd_kernel) ----------------------
    for step in range(1, n_steps):
        peer = {axis_name: my ^ (1 << step)}
        pltpu.semaphore_signal(step_sem.at[step], 1, device_id=peer,
                               device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(step_sem.at[step], 1)
        parity = step % 2
        copies = []
        for c in range(n_chunks):
            copy = pltpu.make_async_remote_copy(
                src_ref=out_ref.at[c],
                dst_ref=recv_ref.at[parity, c],
                send_sem=send_sem.at[c],
                recv_sem=recv_sem.at[c],
                device_id=peer,
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            copy.start()
            copies.append(copy)
        for c in range(n_chunks):
            copies[c].wait()
            out_ref[c] = out_ref[c] + recv_ref[parity, c]


@functools.partial(jax.jit,
                   static_argnames=("axis_name", "n_devices", "n_chunks",
                                    "interpret", "collective_id"))
def fused_matmul_rd_call(x, w, *, axis_name: str, n_devices: int,
                         n_chunks: int, interpret=False,
                         collective_id: int = 8):
    """x: (M, K); w: (n_chunks, K, chunk_d) -> (n_chunks, M, chunk_d)
    RD-all-reduced over ``axis_name`` (inside shard_map)."""
    m = x.shape[0]
    chunk_d = w.shape[-1]
    out_shape = (n_chunks, m, chunk_d)
    kern = functools.partial(_fused_kernel, axis_name=axis_name,
                             n_devices=n_devices, n_chunks=n_chunks)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2,) + out_shape, x.dtype),        # recv (dbl-buffer)
            pltpu.SemaphoreType.REGULAR(                   # per-step barrier
                (max(1, int(math.log2(n_devices))),)),
            pltpu.SemaphoreType.DMA((n_chunks,)),          # send sems
            pltpu.SemaphoreType.DMA((n_chunks,)),          # recv sems
        ],
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=interpret,
    )(x, w)


def collective_matmul_pallas(x: jax.Array, w: jax.Array, ctx, *,
                             spec: str = "bsf,fd->bsd", chunks: int = 4,
                             interpret=False) -> jax.Array:
    """Wrapper: flatten to the kernel layout, run GEMM+RD(slow) fused, then
    finish the fast-axis reduction with a plain psum.

    Falls back to the portable lax path when the slow axis is absent,
    non-power-of-two, or more than one axis (the same fallbacks
    ``rd_all_reduce`` uses).
    """
    from ...core import hierarchical as hier
    from ...core import overlap as ov

    if len(ctx.tp_slow) != 1:
        return ov.collective_matmul(x, w, ctx, spec=spec, chunks=chunks,
                                    backend="lax")
    axis = ctx.tp_slow[0]
    n = lax.axis_size(axis)
    if n == 1 or (n & (n - 1)):
        return ov.collective_matmul(x, w, ctx, spec=spec, chunks=chunks,
                                    backend="lax")
    d_out = w.shape[-1]
    k_dim = 1
    for s in w.shape[:-1]:
        k_dim *= s
    lead = x.shape[: x.ndim - (w.ndim - 1)]
    xm = x.reshape(-1, k_dim)
    wm = w.reshape(k_dim, d_out)
    # column blocks, chunk width aligned to the 128-lane MXU width
    ce = -(-d_out // chunks)
    ce = ((ce + 127) // 128) * 128
    pad = chunks * ce - d_out
    if pad:
        wm = jnp.pad(wm, ((0, 0), (0, pad)))
    wc = wm.reshape(k_dim, chunks, ce).transpose(1, 0, 2)
    out = fused_matmul_rd_call(xm, wc, axis_name=axis, n_devices=n,
                               n_chunks=chunks, interpret=interpret)
    out = out.transpose(1, 0, 2).reshape(xm.shape[0], chunks * ce)
    if pad:
        out = out[:, :d_out]
    out = out.reshape(lead + (d_out,))
    if ctx.tp_fast:
        out = lax.psum(out, ctx.tp_fast)
    return out


__all__ = ["collective_matmul_pallas", "fused_matmul_rd_call"]
