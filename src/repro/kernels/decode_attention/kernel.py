"""GQA decode attention (flash-decode) for TPU.

The decode phase is memory-bandwidth-bound: one query token attends over the
whole KV cache.  Tiling streams the cache HBM->VMEM in (BK, hd) tiles with
the batch dimension blocked to 8 sublanes; the online-softmax state lives in
f32 VMEM scratch across KV blocks (innermost, sequential).

Per-sequence write positions arrive as a (B,) int32 array; keys at index
> pos[b] (or outside the sliding window) are masked, so one kernel serves
ragged continuous-batching batches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1.0e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, window: int,
                   block_b: int, block_k: int):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[...]                                   # (BB,)
    k_pos = ik * block_k + lax.broadcasted_iota(
        jnp.int32, (block_b, block_k), 1)
    mask = k_pos <= pos[:, None]
    if window > 0:
        mask &= k_pos > pos[:, None] - window

    # Skip blocks beyond every sequence's position.
    @pl.when(ik * block_k <= jnp.max(pos))
    def _compute():
        q = q_ref[:, 0].astype(jnp.float32)              # (BB, hd)
        k = k_ref[:, :, 0].astype(jnp.float32)           # (BB, BK, hd)
        v = v_ref[:, :, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # (BB, BK)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[:, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def _paged_decode_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, scale: float,
                         window: int, block_size: int):
    """Grid (B, Hq, max_blocks).  The KV refs are *physical-block* views:
    the index_map below resolves logical block j of sequence b to physical
    block tbl[b, j] via scalar prefetch, so the gather happens in the DMA
    schedule — the logical (B, S, U, hd) view is never materialized in HBM.
    Every value is 2-D (a (1, hd) query row, (bs, hd) key/value tiles,
    (1, 1) softmax state), the shapes the TPU's vector units tile.
    """
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[b]
    k_pos = j * block_size + lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    mask = k_pos <= pos
    if window > 0:
        mask &= k_pos > pos - window

    # Skip logical blocks entirely beyond this sequence's position (their
    # table entries may point at the trash block).
    @pl.when(j * block_size <= pos)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # (1, hd)
        kb = k_ref[0].astype(jnp.float32)                  # (bs, hd)
        vb = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (1, bs)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                # (1, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (1, hd)
        m_ref[...] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "q_per_kv", "interpret"))
def paged_decode_attention_call(q, k_phys, v_phys, block_tbl, positions, *,
                                window: int, q_per_kv: int,
                                interpret=False):
    """Paged flash-decode: one query token per sequence attends over K/V
    scattered across fixed-size physical blocks.

    q: (B, Hq, hd); k_phys/v_phys: (n_blocks, block_size, Hkv, hd);
    block_tbl: (B, max_blocks) int32 logical->physical; positions: (B,).
    Returns (B, Hq, hd).

    The block table and positions ride in SMEM as scalar-prefetch operands
    (``PrefetchScalarGridSpec``): each grid step (b, h, j) DMAs physical
    block ``tbl[b, j]``'s (block_size, hd) tile of kv head ``h //
    q_per_kv`` HBM->VMEM directly, so non-resident blocks cost nothing.
    Heads are folded into the lane dim (``(..., Hkv * hd)``) so that every
    block's last two dims are (8k, 128m)-tileable; on a TPU that view of
    the (..., Hkv, hd) pool is a relayout copy, which a head-major pool
    would avoid.  Batch is *not* sublane-blocked (unlike the dense
    kernel): each sequence's block list is independent, which trades
    sublane utilization for zero logical-view materialization — the
    PagedAttention layout.
    """
    B, Hq, hd = q.shape
    n_blocks, block_size, Hkv, _ = k_phys.shape
    max_blocks = block_tbl.shape[1]
    grid = (B, Hq, max_blocks)
    kern = functools.partial(_paged_decode_kernel, scale=hd ** -0.5,
                             window=window, block_size=block_size)
    q_rows = q.reshape(B, 1, Hq * hd)
    k_rows = k_phys.reshape(n_blocks, block_size, Hkv * hd)
    v_rows = v_phys.reshape(n_blocks, block_size, Hkv * hd)
    kv_spec = pl.BlockSpec((1, block_size, hd),
                           lambda b, h, j, tbl, pos, qpk=q_per_kv:
                           (tbl[b, j], 0, h // qpk))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda b, h, j, tbl, pos: (b, 0, h)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, hd),
                               lambda b, h, j, tbl, pos: (b, 0, h)),
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_rows.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tbl, positions, q_rows, k_rows, v_rows)
    return out.reshape(B, Hq, hd)


@functools.partial(jax.jit, static_argnames=(
    "window", "q_per_kv", "block_b", "block_k", "interpret"))
def decode_attention_call(q, k, v, positions, *, window: int,
                          q_per_kv: int, block_b: int = 8,
                          block_k: int = 256, interpret=False):
    """q: (B, Hq, hd); k/v: (B, S, Hkv, hd); positions: (B,) int32.
    B pre-padded to block_b, S to block_k.  Returns (B, Hq, hd)."""
    B, Hq, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    grid = (B // block_b, Hq, S // block_k)
    kern = functools.partial(_decode_kernel, scale=hd ** -0.5,
                             window=window, block_b=block_b,
                             block_k=block_k)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b,), lambda ib, h, ik: (ib,)),
            pl.BlockSpec((block_b, 1, hd), lambda ib, h, ik: (ib, h, 0)),
            pl.BlockSpec((block_b, block_k, 1, hd),
                         lambda ib, h, ik, qpk=q_per_kv:
                         (ib, ik, h // qpk, 0)),
            pl.BlockSpec((block_b, block_k, 1, hd),
                         lambda ib, h, ik, qpk=q_per_kv:
                         (ib, ik, h // qpk, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, 1, hd),
                               lambda ib, h, ik: (ib, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_b, hd), jnp.float32),
            pltpu.VMEM((block_b,), jnp.float32),
            pltpu.VMEM((block_b,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(positions, q, k, v)
