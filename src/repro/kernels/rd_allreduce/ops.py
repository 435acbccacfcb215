"""Public wrapper: pad/reshape to the chunked layout and invoke the kernel.

Call *inside* shard_map over the slow axis, exactly like
``repro.core.rd_all_reduce``:

    y = rd_all_reduce_pallas(x_partial, "pod", n_chunks=4)
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .kernel import rd_all_reduce_kernel_call


def rd_all_reduce_pallas(x: jax.Array, axis_name: str, *,
                         n_chunks: int = 4, interpret=False) -> jax.Array:
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    if n & (n - 1):
        # non-power-of-two: same fallback the ppermute path uses
        return lax.psum(x, axis_name)
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    # each chunk a (rows, 128) slab of its own leading index, rows a whole
    # number of native (8 * packing, 128) tiles: a DMA-able slice at any
    # message size (bf16 packs two rows per sublane)
    tile = 8 * max(1, 4 // jnp.dtype(dtype).itemsize) * 128
    ce = -(-flat.shape[0] // n_chunks)
    ce = -(-ce // tile) * tile
    pad = n_chunks * ce - flat.shape[0]
    if pad:
        flat = jnp.pad(flat, (0, pad))
    out = rd_all_reduce_kernel_call(
        flat.reshape(n_chunks, ce // 128, 128), axis_name=axis_name,
        n_devices=n, n_chunks=n_chunks, interpret=interpret)
    out = out.reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(shape).astype(dtype)


__all__ = ["rd_all_reduce_pallas"]
