"""Hierarchical all-reduce strategies (the paper's core contribution, in JAX).

The paper's NVRAR decomposes a multi-node all-reduce into
(1) intra-node reduce-scatter, (2) inter-node recursive doubling,
(3) intra-node all-gather.  Here the "node" boundary is the TPU pod boundary:
fast axes live on ICI, slow axes on DCN.  All functions in this module are
called *inside* ``jax.shard_map``; with empty axis tuples they are identities,
so the same model code runs single-device.

Strategies (selected by ``ParallelCtx.ar_strategy``):

====================  =======================================================
flat                  one XLA all-reduce over all TP axes (NCCL-default
                      analogue; XLA picks its own lowering)
hier_ring             RS(fast) + psum(slow) + AG(fast) (2D-HRA style baseline)
hier_rd               RS(fast) + XOR-peer recursive doubling(slow) + AG(fast)
                      == NVRAR (Algorithm 1) expressed with lax.ppermute
hier_rd_halving       RS(fast) + recursive halving/doubling(slow) + AG(fast)
                      (bandwidth-optimal beyond-paper variant)
====================  =======================================================

Extras mirroring the paper's Sec. 4.2 optimizations where they transfer to
TPU: chunked slow-axis exchange (4.2.1) and an int8-compressed exchange whose
piggybacked scales play the role of the paper's fused payload metadata (4.2.2;
see DESIGN.md for why flag words themselves do not transfer).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .pcontext import ParallelCtx
from ..kernels.rd_allreduce import quant as _q

Axes = Tuple[str, ...]

# ctx.ar_quant level -> wire bits (levels beyond "none"/"auto").
QUANT_BITS = {"int8": 8, "int4": 4}


# ---------------------------------------------------------------------------
# Axis utilities
# ---------------------------------------------------------------------------


def axes_size(axes: Sequence[str]) -> int:
    """Product of axis sizes (static inside shard_map)."""
    n = 1
    for a in axes:
        n *= lax.axis_size(a)
    return n


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def _xor_perm(n: int, stride: int):
    return [(j, j ^ stride) for j in range(n)]


# ---------------------------------------------------------------------------
# Recursive doubling over one (slow) axis  — paper Algorithm 1, inter phase
# ---------------------------------------------------------------------------


def rd_all_reduce(x: jax.Array, axis: str, chunks: int = 1) -> jax.Array:
    """Recursive-doubling all-reduce over ``axis`` via XOR-peer ppermute.

    log2(N) steps; at step i every rank exchanges its full partial sum with
    peer ``rank ^ 2**i`` and reduces locally — exactly Algorithm 1's
    ``RD_inter`` (full-exchange form).  Requires a power-of-two axis size
    (falls back to ``lax.psum`` otherwise, mirroring how NVRAR falls back to
    NCCL on non-power-of-two node counts).

    ``chunks>1`` splits the payload into independently exchanged chunks
    (paper Sec. 4.2.1): each chunk's ppermute/add chain is independent, which
    the TPU scheduler can overlap (exchange of chunk q+1 with reduction of
    chunk q).
    """
    n = lax.axis_size(axis)
    if n == 1:
        return x
    if not _is_pow2(n):
        return lax.psum(x, axis)
    if chunks <= 1:
        y = x
        step = 1
        while step < n:
            y = y + lax.ppermute(y, axis, _xor_perm(n, step))
            step <<= 1
        return y
    # Chunked: flatten, pad to a multiple of `chunks`, exchange per chunk.
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % chunks
    if pad:
        flat = jnp.pad(flat, (0, pad))
    parts = list(jnp.split(flat, chunks))
    step = 1
    while step < n:
        perm = _xor_perm(n, step)
        recv = [lax.ppermute(p, axis, perm) for p in parts]
        parts = [p + r for p, r in zip(parts, recv)]
        step <<= 1
    out = jnp.concatenate(parts)
    if pad:
        out = out[: out.shape[0] - pad]
    return out.reshape(x.shape)


def rd_halving_all_reduce(x: jax.Array, axis: str) -> jax.Array:
    """Recursive-halving reduce-scatter + recursive-doubling all-gather.

    Bandwidth-optimal all-reduce over the slow axis: step i of the RS phase
    exchanges half of the remaining payload with peer ``rank ^ 2**i``; the AG
    phase mirrors it.  Total payload 2(N-1)/N |M| vs Algorithm 1's
    log2(N) |M|.  Beyond-paper optimization for the medium-message regime.
    """
    n = lax.axis_size(axis)
    if n == 1:
        return x
    if not _is_pow2(n):
        return lax.psum(x, axis)

    shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))

    # --- reduce-scatter by recursive halving -------------------------------
    # Work on a (n, chunk) view; each rank keeps a shrinking slice.  We track
    # the slice implicitly by reordering: at every step each rank splits its
    # current buffer in two halves; which half it keeps depends on its rank
    # bit.  lax.ppermute sends the *other* half.
    buf = flat.reshape(n, -1)  # n logical chunks
    # Textbook recursive halving: at the step with stride s, each rank keeps
    # the half of its current slice that contains its own chunk (decided by
    # the rank bit at that level) and sends the other half to peer rank^s.
    # The kept-slice size is identical on every rank, so halves can be
    # selected branchlessly on the traced rank index.
    idx = lax.axis_index(axis)
    stride = n >> 1
    size = n
    while size > 1:
        half = size // 2
        keep_hi = ((idx // stride) % 2).astype(bool)  # True -> keep upper half
        lower, upper = buf[:half], buf[half:]
        send_buf = jnp.where(keep_hi, lower, upper)
        keep_buf = jnp.where(keep_hi, upper, lower)
        recv = lax.ppermute(send_buf, axis, _xor_perm(n, stride))
        buf = keep_buf + recv
        size = half
        stride >>= 1
    # buf: (1, chunk) — this rank's fully reduced chunk (chunk index == rank
    # bit pattern).  All-gather back by recursive doubling.
    stride = 1
    while stride < n:
        recv = lax.ppermute(buf, axis, _xor_perm(n, stride))
        # Order matters: the peer's slice is adjacent; whether it goes before
        # or after ours depends on the rank bit at this level.
        bit = ((idx // stride) % 2).astype(bool)  # True -> our slice is upper
        buf = jnp.where(bit,
                        jnp.concatenate([recv, buf], axis=0),
                        jnp.concatenate([buf, recv], axis=0))
        stride <<= 1
    out = buf.reshape(-1)
    if pad:
        out = out[: out.shape[0] - pad]
    return out.reshape(shape)


def compressed_rd_all_reduce(x: jax.Array, axis: str,
                             group: int = 128) -> jax.Array:
    """Recursive doubling with int8-quantized exchanges.

    Each step quantizes the outgoing partial sum to int8 with per-group
    (``group`` elements) bf16 scales, exchanges payload+scales (the TPU
    analogue of the paper's eta-packed fused payload), dequantizes and
    reduces in f32.  eta = 1 + 2/group /? (int8 payload is 4x smaller than
    f32; scales add 2/group bytes per element).
    """
    n = lax.axis_size(axis)
    if n == 1:
        return x
    if not _is_pow2(n):
        return lax.psum(x, axis)
    orig_dtype = x.dtype
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % group
    if pad:
        flat = jnp.pad(flat, (0, pad))
    acc = flat
    step = 1
    while step < n:
        g = acc.reshape(-1, group)
        scale = jnp.max(jnp.abs(g), axis=-1, keepdims=True) / 127.0
        scale = jnp.maximum(scale, 1e-30)
        q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
        perm = _xor_perm(n, step)
        q_peer = lax.ppermute(q, axis, perm)
        s_peer = lax.ppermute(scale.astype(jnp.bfloat16), axis, perm)
        acc = acc + (q_peer.astype(jnp.float32)
                     * s_peer.astype(jnp.float32)).reshape(-1)
        step <<= 1
    if pad:
        acc = acc[: acc.shape[0] - pad]
    return acc.reshape(shape).astype(orig_dtype)


# ---------------------------------------------------------------------------
# Quantized collective phases (ar_quant = int8 | int4)
# ---------------------------------------------------------------------------
#
# Flash-Communication-style low-bit wire: every phase of the hierarchical
# all-reduce carries nibble/byte-packed int8 payloads + per-group bf16
# scales (layout contract in kernels.rd_allreduce.quant).  The fast-level
# reduce-scatter becomes an all_to_all on packed data with a local
# dequantize-sum — every rank sums the SAME dequantized values, so the
# reduced result is exactly replicated (no rank drift).  Error feedback:
# the RS phase is where this rank's contribution is quantized, so it
# returns ``err = v - dequant(quantize(v))`` for the caller to re-inject
# into the next step's message (the accumulator rides in the decode
# cache; DESIGN.md §12).  Slow-phase and all-gather requantization of the
# already-reduced partials is NOT captured by EF — it is one rounding of
# the output, not a per-rank bias, and is bounded by the logit-divergence
# gate instead.


def quant_rd_all_reduce(x: jax.Array, axis: str, bits: int) -> jax.Array:
    """Recursive doubling with a symmetric low-bit exchange.

    Unlike :func:`compressed_rd_all_reduce` (which keeps its own
    accumulator unquantized and lets XOR peers drift apart), BOTH sides of
    every step requantize: ``acc <- deq(Q(acc)) + deq(Q(acc_peer))``.
    The two peers of a step hold the same pair {acc, acc_peer}, so they
    compute identical sums — by induction the final accumulator is exactly
    replicated across the axis, which the all-gather phase requires.
    """
    n = lax.axis_size(axis)
    if n == 1:
        return x
    if not _is_pow2(n):
        return lax.psum(x, axis)
    orig_dtype, shape = x.dtype, x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    # Pad to an int8/int4 group-cap multiple; zero pads quantize exactly
    # and group windows stay cap-aligned (chunk-invariance contract).
    pad = (-flat.shape[0]) % 256
    if pad:
        flat = jnp.pad(flat, (0, pad))
    group = _q.GROUP_CAP[bits]
    acc = flat
    step = 1
    while step < n:
        q, s = _q.quantize_pack(acc, bits, group)
        perm = _xor_perm(n, step)
        q_peer = lax.ppermute(q, axis, perm)
        s_peer = lax.ppermute(s, axis, perm)
        acc = (_q.unpack_dequant(q, s, bits, group)
               + _q.unpack_dequant(q_peer, s_peer, bits, group))
        step <<= 1
    if pad:
        acc = acc[: acc.shape[0] - pad]
    return acc.reshape(shape).astype(orig_dtype)


def _pad_last(x: jax.Array, mult: int):
    pad = (-x.shape[-1]) % mult
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, pad


def _quant_rs_one(v: jax.Array, axis: str, dim: int, bits: int,
                  want_err: bool):
    """One-axis reduce-scatter on a packed low-bit wire.

    ``v`` f32; splits ``dim`` into per-rank chunks, all_to_alls the packed
    payload+scales, and dequant-sums locally -> (scattered f32, err f32 or
    None) where ``err = v - deq(Q(v))`` over the full pre-scatter shape.
    """
    n = lax.axis_size(axis)
    if n == 1:
        return v, (jnp.zeros_like(v) if want_err else None)
    dim = dim % v.ndim
    last = v.ndim - 1
    if dim == last:
        shard = v.shape[-1] // n
        group = _q.group_for(shard, bits)
        vq = v.reshape(v.shape[:-1] + (n, shard))
        q, s = _q.quantize_pack(vq, bits, group)
        ax_i = q.ndim - 2
        qx = lax.all_to_all(q, axis, split_axis=ax_i, concat_axis=ax_i)
        sx = lax.all_to_all(s, axis, split_axis=ax_i, concat_axis=ax_i)
        red = _q.unpack_dequant(qx, sx, bits, group).sum(axis=-2)
        err = None
        if want_err:
            err = v - _q.unpack_dequant(q, s, bits, group).reshape(v.shape)
        return red, err
    # Scatter along a non-trailing dim (e.g. the SP sequence dim): groups
    # stay on the feature (last) dim, untouched by the split.
    size = v.shape[dim]
    vm = jnp.moveaxis(v, dim, 0)
    vm = vm.reshape((n, size // n) + vm.shape[1:])
    vmp, pad = (vm, 0)
    if bits == 4 and vm.shape[-1] % 2:
        vmp, pad = _pad_last(vm, 2)
    group = _q.group_for(vmp.shape[-1], bits)
    q, s = _q.quantize_pack(vmp, bits, group)
    qx = lax.all_to_all(q, axis, split_axis=0, concat_axis=0)
    sx = lax.all_to_all(s, axis, split_axis=0, concat_axis=0)
    deq = _q.unpack_dequant(qx, sx, bits, group)
    deq_own = _q.unpack_dequant(q, s, bits, group) if want_err else None
    if pad:
        deq = deq[..., :-pad]
        deq_own = deq_own[..., :-pad] if want_err else None
    red = jnp.moveaxis(deq.sum(axis=0), 0, dim)
    err = None
    if want_err:
        own = jnp.moveaxis(deq_own.reshape((size,) + vm.shape[2:]), 0, dim)
        err = v - own
    return red, err


def _quant_reduce_scatter(v: jax.Array, axes: Axes, dim: int, bits: int,
                          want_err: bool):
    """Reduce-scatter over ``axes`` (applied per axis, innermost last) with
    packed wire.  ``err`` captures the FIRST stage's quantization of ``v``
    (where this rank's own contribution is rounded); later stages
    requantize partial sums, which EF by design does not chase."""
    err = None
    for i, ax in enumerate(axes):
        v, e = _quant_rs_one(v, ax, dim, bits, want_err and i == 0)
        if i == 0:
            err = e
    return v, err


def _quant_ag_one(y: jax.Array, axis: str, dim: int, bits: int):
    n = lax.axis_size(axis)
    if n == 1:
        return y
    dim = dim % y.ndim
    yp, pad = (y, 0)
    if bits == 4 and y.shape[-1] % 2:
        yp, pad = _pad_last(y, 2)
    group = _q.group_for(yp.shape[-1], bits)
    q, s = _q.quantize_pack(yp, bits, group)
    qg = lax.all_gather(q, axis, axis=0, tiled=False)
    sg = lax.all_gather(s, axis, axis=0, tiled=False)
    deq = _q.unpack_dequant(qg, sg, bits, group)      # (n,) + yp.shape
    if pad:
        deq = deq[..., :-pad]
    out = jnp.moveaxis(deq, 0, dim)                   # n right before dim
    return out.reshape(y.shape[:dim] + (n * y.shape[dim],)
                       + y.shape[dim + 1:])


def _quant_all_gather(y: jax.Array, axes: Axes, dim: int,
                      bits: int) -> jax.Array:
    """All-gather over ``axes`` with packed wire — inverse shard order of
    :func:`_quant_reduce_scatter` (innermost axis gathered first)."""
    for ax in reversed(axes):
        y = _quant_ag_one(y, ax, dim, bits)
    return y


def _quant_slow_phase(x: jax.Array, slow: Axes, ctx: ParallelCtx,
                      bits: int) -> jax.Array:
    """Slow-axis phase under ar_quant: recursive-doubling strategies carry
    the quantized exchange; ring/flat hand XLA a bf16 sum (full-precision
    wire at bf16 width, matching the unquantized path's cost model)."""
    for ax in slow:
        if ctx.ar_strategy in ("hier_rd", "hier_rd_halving"):
            x = quant_rd_all_reduce(x, ax, bits)
        else:
            x = lax.psum(x.astype(jnp.bfloat16), ax).astype(x.dtype)
    return x


def _quant_scatter_ok(x: jax.Array, fast: Axes, dim: int,
                      bits: int) -> bool:
    """Static shape guard for the packed RS path: every axis split must
    divide the scatter dim, and an int4 trailing-dim shard must be even
    (nibble pairs); otherwise callers keep full-precision wire."""
    dim = dim % x.ndim
    size = x.shape[dim]
    for ax in fast:
        n = lax.axis_size(ax)
        if size % n:
            return False
        size //= n
    if bits == 4 and dim == x.ndim - 1 and size % 2:
        return False
    return True


def _quant_tp_all_reduce(x: jax.Array, ctx: ParallelCtx, scatter_dim: int,
                         ef: Optional[jax.Array]):
    """Quantized-wire all-reduce: RS(packed) + slow(packed RD) + AG(packed).

    Returns (y, new_ef); ``new_ef`` is None iff ``ef`` is None, otherwise
    the error-feedback residue this rank must re-inject next step."""
    bits = QUANT_BITS[ctx.ar_quant]
    fast, slow = ctx.tp_fast, ctx.tp_slow
    if ctx.ar_strategy == "flat":
        # Single-level group: still quantize the wire — RS+AG over ALL tp
        # axes is the AR-equivalent decomposition with packed payloads.
        fast, slow = slow + fast, ()
    elif not slow and len(fast) > 1:
        fast, slow = fast[-1:], fast[:-1]
    dim = scatter_dim % x.ndim
    v = x.astype(jnp.float32)
    if ef is not None:
        v = v + ef.astype(jnp.float32)
    if not fast:
        # Slow-only TP group: the quantized RD rounds the whole exchange;
        # there is no per-rank RS rounding to feed back, so EF stays zero.
        y = _quant_slow_phase(v, slow, ctx, bits)
        return y.astype(x.dtype), (jnp.zeros_like(v) if ef is not None
                                   else None)
    if not _quant_scatter_ok(x, fast, dim, bits):
        # Shape can't shard cleanly: keep full-precision wire, EF untouched.
        y = lax.psum(x, ctx.tp_slow + ctx.tp_fast)
        return y, (ef if ef is not None else None)
    red, err = _quant_reduce_scatter(v, fast, dim, bits,
                                     want_err=ef is not None)
    if slow:
        red = _quant_slow_phase(red, slow, ctx, bits)
    y = _quant_all_gather(red, fast, dim, bits)
    return y.astype(x.dtype), err


# ---------------------------------------------------------------------------
# The hierarchical all-reduce entry points (used by every TP layer)
# ---------------------------------------------------------------------------


def _resolve_auto(x: jax.Array, ctx: ParallelCtx) -> ParallelCtx:
    """Concretize ar_strategy='auto' for this call site.

    Shapes are static under jit/shard_map, so the dispatch happens at trace
    time: each call site lowers with the strategy the autotuner picks for its
    (message bytes, fast size, slow size, dtype) key."""
    if ctx.ar_strategy != "auto":
        return ctx
    from . import autotune
    msg_bytes = x.size * x.dtype.itemsize
    return autotune.resolve(ctx, msg_bytes, axes_size(ctx.tp_fast),
                            axes_size(ctx.tp_slow), x.dtype.name)


def _slow_phase(x: jax.Array, slow: Axes, ctx: ParallelCtx) -> jax.Array:
    for ax in slow:
        if ctx.ar_strategy == "hier_ring":
            x = lax.psum(x, ax)
        elif ctx.ar_strategy == "hier_rd":
            if ctx.compress_slow:
                x = compressed_rd_all_reduce(x, ax)
            else:
                x = rd_all_reduce(x, ax, chunks=ctx.rd_chunks)
        elif ctx.ar_strategy == "hier_rd_halving":
            x = rd_halving_all_reduce(x, ax)
        else:  # pragma: no cover
            raise ValueError(ctx.ar_strategy)
    return x


def quantized_all_gather(x: jax.Array, axes: Axes, dim: int,
                         group: int = 128) -> jax.Array:
    """All-gather with int8 payload + per-group bf16 scales.

    The gathered value is each shard's FINAL (already-reduced) slice, so
    quantization error does not accumulate across devices — one rounding of
    the output activations (per-128-group scales keep it ~0.3% relative).
    """
    orig_dtype = x.dtype
    moved = jnp.moveaxis(x, dim, -1)
    shape = moved.shape
    flat = moved.reshape(-1)
    pad = (-flat.shape[0]) % group
    if pad:
        flat = jnp.pad(flat, (0, pad))
    g = flat.reshape(-1, group).astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(g), axis=-1, keepdims=True)
                        / 127.0, 1e-30)
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    qg = lax.all_gather(q.reshape(-1), axes, axis=0, tiled=False)
    sg = lax.all_gather(scale.astype(jnp.bfloat16).reshape(-1), axes,
                        axis=0, tiled=False)
    # qg: (n, flat) stacked shards -> dequantize and stitch along dim
    n = qg.shape[0]
    deq = (qg.reshape(n, -1, group).astype(jnp.float32)
           * sg.reshape(n, -1, 1).astype(jnp.float32)).reshape(n, -1)
    if pad:
        deq = deq[:, :-pad]
    out = deq.reshape((n,) + shape)
    out = jnp.concatenate(list(out), axis=-1)
    return jnp.moveaxis(out, -1, dim).astype(orig_dtype)


def _tp_all_reduce_fp(x: jax.Array, ctx: ParallelCtx,
                      scatter_dim: int) -> jax.Array:
    """Full-precision-wire all-reduce body (strategy already resolved)."""
    fast, slow = ctx.tp_fast, ctx.tp_slow
    if (ctx.ar_strategy == "flat" or (not slow and len(fast) <= 1)) \
            and not ctx.quant_ag:
        # Single-level group: hand the whole reduction to XLA (the paper's
        # "NCCL default" baseline) — hierarchy needs two levels to matter.
        return lax.psum(x, slow + fast)
    if not slow and len(fast) > 1:
        # Two+ fast axes (e.g. 256-way TP over ("data","model")): treat the
        # innermost axis as the fast level and the rest as slow-ish levels.
        fast, slow = fast[-1:], fast[:-1]
    dim = scatter_dim % x.ndim
    if not fast:
        return _slow_phase(x, slow, ctx)
    # Phase 1: reduce-scatter over the fast level (paper Eq. 3).
    y = lax.psum_scatter(x, fast, scatter_dimension=dim, tiled=True)
    # Phase 2: recursive doubling (or ring) over the slow level (Eq. 4).
    if slow:
        y = _slow_phase(y, slow, ctx if ctx.ar_strategy != "flat"
                        else ctx.replace(ar_strategy="hier_ring"))
    # Phase 3: all-gather over the fast level (Eq. 5).
    if ctx.quant_ag:
        return quantized_all_gather(y, fast, dim)
    return lax.all_gather(y, fast, axis=dim, tiled=True)


def tp_all_reduce(x: jax.Array, ctx: ParallelCtx, scatter_dim: int = -1,
                  ef: Optional[jax.Array] = None):
    """All-reduce a TP partial sum according to the configured strategy.

    This is the operation the paper optimizes: in decode it runs twice per
    transformer layer on a (B, 1, d_model) tensor (the B x H small-message
    regime of Sec. 3.5).

    ``scatter_dim`` is the dimension along which the hierarchical strategies
    reduce-scatter over the fast axes (must be divisible by the fast-axes
    size; model dims here always are — validated at config time).

    ``ctx.ar_quant`` in {int8, int4} (forced, or resolved per call site by
    the autotuner when ar_quant="auto") routes through the packed low-bit
    wire.  ``ef`` is the error-feedback accumulator for this call site:
    when given, the call returns ``(y, new_ef)`` — the quantized paths add
    ``ef`` to the outgoing message and return the fresh rounding residue;
    unquantized paths pass ``ef`` through untouched — so call sites can
    thread EF unconditionally and let dispatch decide.  Without ``ef`` the
    return is the plain array (lossy levels then quantize one-shot).
    """
    fast, slow = ctx.tp_fast, ctx.tp_slow
    if not fast and not slow:
        return (x, ef) if ef is not None else x
    ctx = _resolve_auto(x, ctx)
    with jax.named_scope("tp_all_reduce"):
        if ctx.ar_quant in QUANT_BITS:
            y, ef2 = _quant_tp_all_reduce(x, ctx, scatter_dim, ef)
            return (y, ef2) if ef is not None else y
        y = _tp_all_reduce_fp(x, ctx, scatter_dim)
    return (y, ef) if ef is not None else y


def tp_reduce_scatter(x: jax.Array, ctx: ParallelCtx,
                      dim: int) -> jax.Array:
    """Sequence-parallel form: reduce TP partials, leave result sharded on
    ``dim`` over the fast axes (Megatron-SP).  Slow-axis phase still runs in
    full so the result is correct across pods.

    Slow-phase strategy selection mirrors :func:`tp_all_reduce`: ``flat``
    hands the cross-pod sum to XLA (``lax.psum``), every hierarchical
    strategy runs its own inter phase via ``_slow_phase`` (ring / recursive
    doubling / halving).  (PR 5 bugfix: this used to bury the flat case in
    a conditional that could never fire, so ``hier_ring`` bypassed
    ``_slow_phase`` and ``flat`` was selected by dead code.)
    """
    fast, slow = ctx.tp_fast, ctx.tp_slow
    if not fast and not slow:
        return x
    ctx = _resolve_auto(x, ctx)
    dim = dim % x.ndim
    with jax.named_scope("tp_reduce_scatter"):
        if ctx.ar_quant in QUANT_BITS and fast \
                and _quant_scatter_ok(x, fast, dim, QUANT_BITS[ctx.ar_quant]):
            bits = QUANT_BITS[ctx.ar_quant]
            y, _ = _quant_reduce_scatter(x.astype(jnp.float32), fast, dim,
                                         bits, want_err=False)
            if slow:
                y = _quant_slow_phase(y, slow, ctx, bits)
            return y.astype(x.dtype)
        if fast:
            x = lax.psum_scatter(x, fast, scatter_dimension=dim, tiled=True)
        if slow:
            if ctx.ar_strategy == "flat":
                x = lax.psum(x, slow)
            else:
                x = _slow_phase(x, slow, ctx)
    return x


def tp_all_gather(x: jax.Array, ctx: ParallelCtx, dim: int) -> jax.Array:
    """Gather a sequence-sharded activation back to full along ``dim``."""
    if not ctx.tp_fast:
        return x
    with jax.named_scope("tp_all_gather"):
        if ctx.ar_quant in QUANT_BITS:
            return _quant_all_gather(x.astype(jnp.float32), ctx.tp_fast,
                                     dim % x.ndim,
                                     QUANT_BITS[ctx.ar_quant]).astype(x.dtype)
        if ctx.quant_ag:
            return quantized_all_gather(x, ctx.tp_fast, dim % x.ndim)
        return lax.all_gather(x, ctx.tp_fast, axis=dim % x.ndim, tiled=True)


# ---------------------------------------------------------------------------
# Gradient reduction across pods (training integration of the technique)
# ---------------------------------------------------------------------------


def grad_cross_pod_reduce(grads, ctx: ParallelCtx, pod_axes: Axes):
    """Reduce gradients across the slow (pod) axes.

    Gradients are already reduce-scattered over the FSDP axis by AD; what
    remains is the cross-pod sum — the exact regime of the paper's inter-node
    phase.  Strategy per ``ctx.grad_reduce_strategy``:
      flat     - lax.psum (XLA default)
      rd       - recursive doubling (NVRAR inter-node phase)
      rd_int8  - recursive doubling with int8-compressed exchange
    """
    if not pod_axes:
        return grads
    strat = ctx.grad_reduce_strategy

    def red(g):
        out = g
        for ax in pod_axes:
            if strat == "flat":
                out = lax.psum(out, ax)
            elif strat == "rd":
                out = rd_all_reduce(out, ax, chunks=ctx.rd_chunks)
            elif strat == "rd_int8":
                out = compressed_rd_all_reduce(out, ax)
            else:  # pragma: no cover
                raise ValueError(strat)
        return out

    return jax.tree.map(red, grads)


def dp_psum_mean(x: jax.Array, ctx: ParallelCtx) -> jax.Array:
    """Mean over all batch axes (for loss/metric aggregation)."""
    axes = ctx.dp
    if not axes:
        return x
    return lax.psum(x, axes) / axes_size(axes)


__all__ = [
    "rd_all_reduce", "rd_halving_all_reduce", "compressed_rd_all_reduce",
    "quant_rd_all_reduce", "tp_all_reduce", "tp_reduce_scatter",
    "tp_all_gather", "grad_cross_pod_reduce", "dp_psum_mean", "axes_size",
    "QUANT_BITS",
]
