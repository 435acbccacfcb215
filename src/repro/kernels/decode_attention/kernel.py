"""GQA decode attention (flash-decode) for TPU.

The decode phase is memory-bandwidth-bound: one query token attends over the
whole KV cache.  Tiling streams the cache HBM->VMEM in (BK, hd) tiles with
the batch dimension blocked to 8 sublanes; the online-softmax state lives in
f32 VMEM scratch across KV blocks (innermost, sequential).

Per-sequence write positions arrive as a (B,) int32 array; keys at index
> pos[b] (or outside the sliding window) are masked, so one kernel serves
ragged continuous-batching batches.

The paged kernel (``paged_decode_attention``, the served decode path's
attention on a TPU) reads a paged pool in place instead: each slot copies
only its live blocks through the block table, whole blocks at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
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


def _paged_decode_kernel(lens_ref, tbl_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, parity_ref, *, group: int,
                         max_blocks: int, window: int, scale: float):
    """Grid (B,), sequential.  Program ``b`` attends slot ``b``'s query
    heads over the live blocks of its row of the table, read where they
    lie in the pool, ``group`` blocks per iteration of a loop whose trip
    count comes from the slot's length (none for a slot of length 0, its
    output zeros).  Every block copy carries all the KV heads of the
    block; the next group's copies (the next slot's first group, after a
    slot's last) run while this one is computed.

    The query heads of all KV heads score against the group's
    (group*bs*U, hd) K rows in one product, a key column counting for the
    query rows of its own head only: a block (bs, U, hd) is then used as
    it lies, with no per-head relayout in VMEM.
    """
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    _, _, bs, U, hd = k_buf.shape
    Hq = q_ref.shape[0]
    g = Hq // U
    rows = group * bs * U

    def span(s):
        """(length, first block, end block, groups) of slot ``s``."""
        n = lens_ref[s]
        hi = jnp.minimum((n + bs - 1) // bs, max_blocks)
        lo = jnp.maximum(n - window, 0) // bs if window > 0 else 0
        return n, lo, hi, (hi - lo + group - 1) // group

    def dma(s, grp, slot, wait: bool):
        _, lo, hi, _ = span(s)
        first = lo + grp * group
        for j in range(group):
            @pl.when(first + j < hi)
            def _():
                phys = tbl_ref[s * max_blocks + first + j]
                for i, (src, dst) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf))):
                    cp = pltpu.make_async_copy(src.at[phys], dst.at[slot, j],
                                               sems.at[i, slot])
                    if wait:
                        cp.wait()
                    else:
                        cp.start()

    @pl.when(b == 0)
    def _first():
        parity_ref[0] = 0

    n, lo, _, n_grp = span(b)
    p0 = parity_ref[0]

    # the previous slot's last group started this slot's first, unless
    # it read nothing
    @pl.when((n_grp > 0) & ((b == 0) | (span(jnp.maximum(b - 1, 0))[3]
                                          == 0)))
    def _own_first():
        dma(b, 0, p0, wait=False)

    q = q_ref[...]
    col = lax.broadcasted_iota(jnp.int32, (Hq, rows), 1)
    own_head = lax.broadcasted_iota(jnp.int32, (Hq, rows), 0) // g \
        == col % U
    col_pos = col // U
    row_pos = lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // U

    def live(kpos):
        ok = kpos < n
        if window > 0:
            ok &= kpos >= n - window
        return ok

    def body(i, carry):
        m_prev, l_prev, acc = carry
        slot = (p0 + i) % 2

        @pl.when(i + 1 < n_grp)
        def _next_group():
            dma(b, i + 1, 1 - slot, wait=False)

        @pl.when((i + 1 == n_grp) & (b + 1 < n_slots))
        def _next_slot():
            dma(b + 1, 0, 1 - slot, wait=False)

        dma(b, i, slot, wait=True)
        base = (lo + i * group) * bs
        k = k_buf[slot].reshape(rows, hd)
        # rows past the slot's length hold whatever the buffer held
        v = jnp.where(live(base + row_pos), v_buf[slot].reshape(rows, hd),
                      0)
        ok = own_head & live(base + col_pos)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m, l, acc = lax.fori_loop(
        0, n_grp, body,
        (jnp.full((Hq, 1), NEG_INF, jnp.float32),
         jnp.zeros((Hq, 1), jnp.float32),
         jnp.zeros((Hq, hd), jnp.float32)))
    parity_ref[0] = (p0 + n_grp) % 2
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_group(block_shape, itemsize: int, max_blocks: int) -> int:
    """Blocks a slot reads per loop iteration: enough K rows (positions
    times KV heads) to fill the 128 lanes of the scores, and about 256 KiB
    of K, so that the copies in flight keep HBM busy (on a v5e, one layer
    of the codeqwen cell's 64 slots at contexts 64-800 read in 0.262 ms at
    16 blocks against 0.280 at 8, and Mistral's 32 full slots at tp=4 in
    0.444 ms at 32 against 0.490 at 16)."""
    bs, U = block_shape[0], block_shape[1]
    per_block = int(np.prod(block_shape)) * itemsize
    return max(1, min(max_blocks, max(-(-128 // (bs * U)),
                                      (256 << 10) // per_block)))


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q, k_pool, v_pool, block_tbl, lengths, *,
                           window: int = 0, interpret=False):
    """Paged flash-decode over the live blocks of each slot, in place.

    q: (B, Hq, hd); k_pool/v_pool: (n_blocks, bs, U, hd), the pool as the
    cache holds it (a layer's blocks of the stacked pool, through a table
    offset to them); block_tbl: (B, max_blocks) int32 logical->physical;
    lengths: (B,) int32 positions each slot attends (``positions + 1``
    after the step's K/V write; 0 reads nothing and returns zeros).
    Returns (B, Hq, hd).  Table entries past a slot's length may point
    anywhere in the pool.

    The table and the lengths ride in SMEM (scalar prefetch); the pools
    stay in HBM and slot ``b`` copies only blocks ``[lo, ceil(len/bs))``
    of its row, ``lo`` the first block inside the sliding window (0
    without one).  Positions past the length score ``NEG_INF`` and weigh
    nothing, as in the gathered path; the softmax state and accumulators
    are f32, K and V stay in the pool's dtype.
    """
    B, Hq, hd = q.shape
    _, bs, U, _ = k_pool.shape
    max_blocks = block_tbl.shape[1]
    group = paged_group(k_pool.shape[1:], k_pool.dtype.itemsize, max_blocks)
    kern = functools.partial(_paged_decode_kernel, group=group,
                             max_blocks=max_blocks, window=window,
                             scale=hd ** -0.5)
    buf = pltpu.VMEM((2, group, bs, U, hd), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((None, Hq, hd), lambda b, n, t: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, Hq, hd), lambda b, n, t: (b, 0, 0)),
        scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tbl.reshape(-1).astype(jnp.int32),
      q, k_pool, v_pool)


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
