"""Recursive-doubling all-reduce as a single Pallas TPU kernel.

This is the TPU-native re-expression of the paper's NVRAR inter-node phase
(Algorithm 1, ``RD_inter``): log2(N) XOR-peer exchange steps, each sending
the full partial sum, chunked into ``n_chunks`` independently-DMA'd pieces so
the reduction of chunk q overlaps the transfer of chunk q+1 (paper
Sec. 4.2.1's chunked non-blocking communication).

GPU->TPU mechanism mapping (DESIGN.md §2):
  NVSHMEM put_nbi            -> pltpu.make_async_remote_copy(...).start()
  LL fused data+flag payload -> hardware DMA completion semaphores
                                (recv_sem) — no flag words needed
  sequence-number sync       -> per-step barrier semaphore handshake with
                                the peer (prevents recv-buffer reuse races)

The kernel exchanges over one mesh axis (the slow/DCN axis) inside
shard_map, on a mesh of any rank; x must be the caller's partial sum,
laid out as (n_chunks, rows, 128) with rows a multiple of the dtype's
native tile height (8 for f32, 16 for bf16), so that each chunk is a
DMA-able slice on a TPU (``ops.rd_all_reduce_pallas`` pads to that).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def enter_barrier(axis_name: str, my, n_steps: int):
    """Wait until every RD peer of this device has entered the kernel, so
    its scratch semaphores and receive buffer exist before any signal or
    DMA lands there.  Uses the kernel's barrier semaphore, which is what
    the ``collective_id`` compiler param names."""
    barrier = pltpu.get_barrier_semaphore()
    for step in range(n_steps):
        pltpu.semaphore_signal(barrier, 1,
                               device_id={axis_name: my ^ (1 << step)},
                               device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(barrier, n_steps)


def _rd_kernel(x_ref, out_ref, recv_ref, step_sem, send_sem, recv_sem, *,
               axis_name: str, n_devices: int, n_chunks: int):
    my = lax.axis_index(axis_name)
    out_ref[...] = x_ref[...]
    n_steps = int(math.log2(n_devices))
    enter_barrier(axis_name, my, n_steps)

    for step in range(n_steps):
        # the peer differs from this device in ``axis_name`` only; MESH
        # addressing keeps every other mesh coordinate (a scalar LOGICAL id
        # would name the wrong device on a mesh with more than one axis)
        peer = {axis_name: my ^ (1 << step)}
        # --- per-step peer handshake (replaces the paper's sequence
        # numbers): both sides signal + wait so the peer's recv buffer for
        # this step parity is known-free before any chunk lands.  The
        # semaphore is indexed BY STEP: a single shared barrier would let a
        # fast device's step-(i+1) signal satisfy a slow device's step-i
        # wait (the race the paper's sequence numbers also prevent).
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
            copy.start()           # non-blocking put (put_nbi analogue)
            copies.append(copy)
        for c in range(n_chunks):
            copies[c].wait()        # send done (our buffer reusable) +
            #                         recv done (peer's chunk arrived)
            out_ref[c] = out_ref[c] + recv_ref[parity, c]


@functools.partial(jax.jit,
                   static_argnames=("axis_name", "n_devices", "n_chunks",
                                    "interpret", "collective_id"))
def rd_all_reduce_kernel_call(x, *, axis_name: str, n_devices: int,
                              n_chunks: int = 1, interpret=False,
                              collective_id: int = 7):
    """x: (n_chunks, rows, 128) f32/bf16 partial sum (inside shard_map)."""
    kern = functools.partial(_rd_kernel, axis_name=axis_name,
                             n_devices=n_devices, n_chunks=n_chunks)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2,) + tuple(x.shape), x.dtype),   # recv (dbl-buffer)
            pltpu.SemaphoreType.REGULAR(                   # per-step barrier
                (max(1, int(math.log2(n_devices))),)),
            pltpu.SemaphoreType.DMA((n_chunks,)),          # send sems
            pltpu.SemaphoreType.DMA((n_chunks,)),          # recv sems
        ],
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=interpret,
    )(x)
