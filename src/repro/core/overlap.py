"""Overlapped collective-matmul: the TP output-projection + all-reduce pair
as one software-pipelined primitive.

In decode, every transformer layer ends with two row-parallel GEMMs
(attention wo, MLP down-projection) whose partial sums are immediately
all-reduced — and the paper shows that all-reduce dominating multi-node step
time.  Running the GEMM to completion *then* reducing serializes compute and
communication; Flash-Communication-style chunking recovers the overlap:

    split the output features D into K chunks
    for q in 0..K-1:   partial_q = x @ w[:, q]        (GEMM chunk q)
                       y_q = tp_all_reduce(partial_q) (comm chunk q)
    y = concat(y_0..y_{K-1})

Chunk q's all-reduce has no data dependency on chunk q+1's GEMM, so the XLA
latency-hiding scheduler can run them concurrently (the same independence
idiom ``rd_all_reduce``'s chunked slow-axis exchange relies on).  Because the
split is along the *output* dimension, every output element is produced by
exactly the same dot product and reduction tree as the unchunked path — the
result is bit-consistent with GEMM-then-``tp_all_reduce`` (a strict
requirement: decode greedy tokens must not depend on the overlap knob).

Total wire bytes are unchanged.  With ``ar_strategy="auto"`` the dispatch is
resolved ONCE from the unchunked projection output and shared by every
chunk: a per-chunk lookup on the |M|/K message could select a different
strategy (a different device-sum order) than the unfused path and void the
bit-consistency guarantee above.

The quantized wire (``ar_quant``) composes with chunking.  Its quantization
groups are cap-aligned windows along the trailing feature dim
(``kernels.rd_allreduce.quant``), so when both the full output and every
chunk's per-rank scattered shard are multiples of the group cap, the chunked
path quantizes exactly the same absolute feature windows as the unchunked
one — bit-identical output, overlap preserved (:func:`_quant_chunk_ok`).
Misaligned shapes fall back to one message rather than silently changing
numerics.  Error feedback rides along: the EF buffer is sliced per chunk on
the same feature boundaries and the per-chunk residuals concat back.

A Pallas TPU variant that fuses the slow-axis RD exchange into the GEMM
epilogue lives in ``repro.kernels.rd_allreduce.fused_matmul`` (selected with
``backend="pallas"``); this module's lax implementation is the portable
default and the parity reference.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import hierarchical as hier
from ..kernels.rd_allreduce import quant as _q
from .pcontext import ParallelCtx


def _resolve_auto_for_matmul(x: jax.Array, w: jax.Array,
                             ctx: ParallelCtx) -> ParallelCtx:
    """Concretize ar_strategy='auto' from the UNCHUNKED projection output.

    Resolution must happen once, before chunking: a per-chunk lookup on the
    |M|/K message could pick a different strategy (a different device-sum
    order) than the unfused path and void the bit-consistency guarantee."""
    if ctx.ar_strategy != "auto":
        return ctx
    from . import autotune
    out_elems = w.shape[-1]
    for s in x.shape[: x.ndim - (w.ndim - 1)]:
        out_elems *= s
    dt = jnp.result_type(x, w)
    return autotune.resolve(ctx, out_elems * dt.itemsize,
                            hier.axes_size(ctx.tp_fast),
                            hier.axes_size(ctx.tp_slow), dt.name)


def _resolve_chunks(d_out: int, fast_size: int, requested: int) -> int:
    """Largest chunk count <= requested that divides d_out into equal chunks
    each still divisible by the fast-axis size (psum_scatter tiling needs
    chunk_len % fast == 0)."""
    k = max(1, min(requested, d_out))
    while k > 1 and (d_out % k or (d_out // k) % max(1, fast_size)):
        k -= 1
    return k


def _quant_chunk_ok(d_out: int, k: int, n_scatter: int, bits: int) -> bool:
    """True when chunking into ``k`` pieces is bit-identical to the
    unchunked quantized all-reduce.

    The quantized wire groups cap-aligned windows along the trailing
    feature dim of each rank's scattered shard.  When both the full
    output (``d_out / n_scatter``) and every chunk's shard
    (``d_out / k / n_scatter``) are multiples of the group cap, chunked
    and unchunked paths quantize the same absolute feature windows with
    the same scales — so the overlap knob stays numerics-free.  Any
    misalignment shifts group boundaries and must fall back to one
    message."""
    cap = _q.GROUP_CAP[bits] * max(1, n_scatter)
    return d_out % cap == 0 and (d_out // k) % cap == 0


def collective_matmul(x: jax.Array, w: jax.Array, ctx: ParallelCtx, *,
                      spec: str = "bsf,fd->bsd",
                      chunks: Optional[int] = None,
                      backend: str = "lax", ef=None):
    """Row-parallel projection fused with its TP all-reduce.

    x: local activation shard (the einsum lhs); w: this device's weight shard
    whose **last dim is the replicated output features** (einsum rhs);
    ``spec``: einsum spec mapping (x, w) -> partial output with the feature
    dim last (e.g. ``"bsqh,qhd->bsd"`` for attention wo, ``"bsf,fd->bsd"``
    for the MLP down-projection).

    Returns the **fully reduced** output (what GEMM + ``tp_all_reduce``
    would produce), with chunk q's reduction overlapped against chunk q+1's
    GEMM when ``chunks > 1``.

    ``ef``: optional error-feedback residual with the output's shape.  When
    given, the return value is ``(y, new_ef)`` — same contract as
    ``tp_all_reduce``; the residual is sliced per chunk along the feature
    dim so chunked and unchunked EF states are element-identical.
    """
    if chunks is None:
        chunks = ctx.overlap_chunks if ctx.overlap_matmul else 1
    if not ctx.has_tp:
        y = jnp.einsum(spec, x, w)
        return (y, ef) if ef is not None else y
    d_out = w.shape[-1]
    fast_n = hier.axes_size(ctx.tp_fast)
    ctx = _resolve_auto_for_matmul(x, w, ctx)
    k = _resolve_chunks(d_out, fast_n, chunks)
    bits = hier.QUANT_BITS.get(ctx.ar_quant)
    if bits is not None and k > 1:
        # Quantized wire: chunking is allowed exactly when the
        # chunk shards stay group-cap aligned (see _quant_chunk_ok); the
        # autotuner already scored this call site on the unchunked message,
        # so a misaligned fallback only loses overlap, never dispatch.
        n_tp = fast_n * hier.axes_size(ctx.tp_slow)
        if not _quant_chunk_ok(d_out, k, n_tp, bits):
            k = 1
    if backend == "pallas" and ctx.tp_slow and bits is None and ef is None:
        from ..kernels.rd_allreduce.fused_matmul import (
            collective_matmul_pallas)
        return collective_matmul_pallas(x, w, ctx, spec=spec, chunks=k)
    if k <= 1:
        return hier.tp_all_reduce(jnp.einsum(spec, x, w), ctx,
                                  scatter_dim=-1, ef=ef)
    step = d_out // k
    outs, errs = [], []
    for q in range(k):
        wq = lax.slice_in_dim(w, q * step, (q + 1) * step, axis=-1)
        partial = jnp.einsum(spec, x, wq)
        if ef is None:
            outs.append(hier.tp_all_reduce(partial, ctx, scatter_dim=-1))
        else:
            eq = lax.slice_in_dim(ef, q * step, (q + 1) * step, axis=-1)
            yq, eq2 = hier.tp_all_reduce(partial, ctx, scatter_dim=-1,
                                         ef=eq)
            outs.append(yq)
            errs.append(eq2)
    y = jnp.concatenate(outs, axis=-1)
    if ef is not None:
        return y, jnp.concatenate(errs, axis=-1)
    return y


def collective_matmul_reduce_scatter(x: jax.Array, w: jax.Array,
                                     ctx: ParallelCtx, *, dim: int,
                                     spec: str = "bsf,fd->bsd",
                                     chunks: Optional[int] = None
                                     ) -> jax.Array:
    """Sequence-parallel variant: chunked GEMM pipelined against
    ``tp_reduce_scatter`` (Megatron-SP's projection + RS pair).  The scatter
    runs along ``dim`` (sequence), the chunking along the feature dim, so
    the two never interact and the concat order is preserved."""
    if chunks is None:
        chunks = ctx.overlap_chunks if ctx.overlap_matmul else 1
    if not ctx.has_tp:
        return jnp.einsum(spec, x, w)
    d_out = w.shape[-1]
    ctx = _resolve_auto_for_matmul(x, w, ctx)
    k = _resolve_chunks(d_out, 1, chunks)
    bits = hier.QUANT_BITS.get(ctx.ar_quant)
    if bits is not None and k > 1 and not _quant_chunk_ok(d_out, k, 1,
                                                          bits):
        # RS scatters along the sequence dim; quant groups live on the
        # feature dim, so only feature-cap alignment matters (n_scatter=1).
        k = 1
    if k <= 1:
        return hier.tp_reduce_scatter(jnp.einsum(spec, x, w), ctx, dim=dim)
    step = d_out // k
    outs = []
    for q in range(k):
        wq = lax.slice_in_dim(w, q * step, (q + 1) * step, axis=-1)
        outs.append(hier.tp_reduce_scatter(jnp.einsum(spec, x, wq), ctx,
                                           dim=dim))
    return jnp.concatenate(outs, axis=-1)


__all__ = ["collective_matmul", "collective_matmul_reduce_scatter"]
