"""Core transformer layers, written against local (per-device) shapes.

Every function here runs either single-device (``ParallelCtx`` with no axes;
shapes are the logical model shapes) or inside ``jax.shard_map`` on a
production mesh (shapes are the per-device shards produced by the sharding
specs in :mod:`repro.parallel.sharding`).  TP partial sums are *returned* by
layers; callers reduce them with :func:`repro.core.tp_all_reduce` (decode, the
paper's regime) or :func:`repro.core.tp_reduce_scatter` (sequence-parallel
training) so the all-reduce strategy stays a deployment decision.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.pcontext import ParallelCtx
from ..core import hierarchical as hier
from ..core import overlap as ov
from ..kernels.decode_attention import paged_decode_attention
from .common import ModelConfig, GQAPlan, dense_init, split_keys, place_heads

Params = Dict[str, jax.Array]

# ---------------------------------------------------------------------------
# Rank helpers (device-dependent constants under shard_map)
# ---------------------------------------------------------------------------


def tp_rank(ctx: ParallelCtx):
    """Linearized rank within the TP group (slow axes outermost), matching
    how PartitionSpec ``(slow..., fast...)`` slices a sharded dimension."""
    axes = ctx.tp_slow + ctx.tp_fast
    if not axes:
        return jnp.int32(0)
    r = jnp.int32(0)
    for a in axes:
        r = r * lax.axis_size(a) + lax.axis_index(a)
    return r


def take_local(table: np.ndarray, ctx: ParallelCtx) -> jax.Array:
    """Select this device's row of a small per-rank constant table."""
    t = jnp.asarray(table)
    return jnp.take(t, tp_rank(ctx), axis=0)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * lax.rsqrt(var + eps)).astype(x.dtype)
            * w.astype(x.dtype))


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return y.astype(x.dtype) * w.astype(x.dtype) + b.astype(x.dtype)


def apply_norm(x: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    if cfg.norm == "layernorm":
        return layer_norm(x, p["w"], p["b"], cfg.norm_eps)
    return rms_norm(x, p["w"], cfg.norm_eps)


def init_norm(cfg: ModelConfig, d: Optional[int] = None) -> Params:
    d = d or cfg.d_model
    p = {"w": jnp.ones((d,), cfg.dtype)}
    if cfg.norm == "layernorm":
        p["b"] = jnp.zeros((d,), cfg.dtype)
    return p


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_tables(positions: jax.Array, head_dim: int,
                theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions: (..., S) int32 -> cos/sin (..., S, head_dim/2), f32."""
    half = head_dim // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, N, hd); cos/sin: (B, S, hd/2) or (S, hd/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos_, sin_ = cos[None, :, None, :], sin[None, :, None, :]
    else:
        cos_, sin_ = cos[:, :, None, :], sin[:, :, None, :]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos_ - x2f * sin_,
                           x2f * cos_ + x1f * sin_], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

NEG_INF = -1.0e30


def init_attention(key, cfg: ModelConfig, plan: GQAPlan,
                   d_model: Optional[int] = None) -> Params:
    """Weights in the *padded global slot layout* (shardable on the slot
    axis).  Live slots get fresh init; dead/replicated slots follow the map.
    """
    d = d_model or cfg.d_model
    hd = cfg.head_dim
    kq, kk, kv_, ko = split_keys(key, 4)
    wq = dense_init(kq, (cfg.n_heads, d, hd), d, cfg.dtype)
    wk = dense_init(kk, (cfg.n_kv_heads, d, hd), d, cfg.dtype)
    wv = dense_init(kv_, (cfg.n_kv_heads, d, hd), d, cfg.dtype)
    wo = dense_init(ko, (cfg.n_heads, hd, d), cfg.n_heads * hd, cfg.dtype)
    p = {
        "wq": place_heads(wq, plan.q_map).transpose(1, 0, 2),   # (D, Q, hd)
        "wk": place_heads(wk, plan.kv_map).transpose(1, 0, 2),  # (D, U, hd)
        "wv": place_heads(wv, plan.kv_map).transpose(1, 0, 2),
        "wo": place_heads(wo, plan.q_map),                      # (Q, hd, D)
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((plan.q_slots, hd), cfg.dtype)
        p["bk"] = jnp.zeros((plan.kv_slots, hd), cfg.dtype)
        p["bv"] = jnp.zeros((plan.kv_slots, hd), cfg.dtype)
    return p


def _qkv(p: Params, h: jax.Array, plan: GQAPlan):
    q = jnp.einsum("bsd,dqh->bsqh", h, p["wq"])
    k = jnp.einsum("bsd,duh->bsuh", h, p["wk"])
    v = jnp.einsum("bsd,duh->bsuh", h, p["wv"])
    if "bq" in p:
        # Under shard_map the biases are already this device's slot slice.
        q = q + p["bq"][None, None]
        k = k + p["bk"][None, None]
        v = v + p["bv"][None, None]
    return q, k, v


def _qkv_rope(p: Params, h: jax.Array, cfg: ModelConfig, plan: GQAPlan,
              positions: jax.Array):
    """Q, K and V of ``h`` with RoPE at ``positions`` ((B, S) or (S,))."""
    with jax.named_scope("qkv"):
        q, k, v = _qkv(p, h, plan)
        if cfg.rope_theta > 0:
            cos, sin = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
    return q, k, v


def _o_proj(o: jax.Array, wo: jax.Array) -> jax.Array:
    with jax.named_scope("o_proj"):
        return jnp.einsum("bsqh,qhd->bsd", o, wo)


def _mask(q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
          window: int, k_valid: Optional[jax.Array] = None) -> jax.Array:
    """Boolean mask (..., Sq, Sk).  q_pos: (..., Sq); k_pos: (Sk,) or (..., Sk)."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :] if k_pos.ndim == q_pos.ndim else k_pos[None, :]
    m = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        m &= kp <= qp
    if window > 0:
        m &= kp > qp - window
    if k_valid is not None:
        kv_ = k_valid[..., None, :] if k_valid.ndim == q_pos.ndim else k_valid[None, :]
        m &= kv_
    return m


def attn_core(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array,
              g: int, *, chunk: int = 0, k_scale=None,
              v_scale=None) -> jax.Array:
    """Grouped attention.  q: (B,Sq,U*g,hd), k/v: (B,Sk,U,hd),
    mask: (B,Sq,Sk) or (Sq,Sk) bool.  Returns (B,Sq,U*g,hd).

    ``k_scale``/``v_scale`` ((B,Sk,U) bf16) dequantize int8 K/V caches
    chunk-by-chunk (the cache is streamed, never materialized in bf16).
    """
    B, Sq, QL, hd = q.shape
    U = k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Sq, U, g, hd)
    if mask.ndim == 2:
        mask = mask[None]
    if chunk and k.shape[1] > chunk:
        return _attn_chunked(qg, k, v, mask, scale, chunk=chunk,
                             k_scale=k_scale, v_scale=v_scale
                             ).reshape(B, Sq, QL, hd)
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale[..., None].astype(jnp.float32)
        v = v.astype(jnp.float32) * v_scale[..., None].astype(jnp.float32)
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    s = jnp.einsum("bsugh,btuh->bugst", qg, k).astype(jnp.float32) * scale
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bugst,btuh->bsugh", p, v)
    return o.reshape(B, Sq, QL, hd)


def _attn_chunked(qg, k, v, mask, scale, chunk: int = 1024,
                  k_scale=None, v_scale=None):
    """Online-softmax attention, scanned over KV chunks (inference paths for
    long sequences).  Chunks are sliced inside the scan body — the cache is
    streamed, never copied/transposed, so peak extra memory is
    O(Sq * chunk) and the KV bytes-accessed term is the cache read itself."""
    B, Sq, U, g, hd = qg.shape
    Sk = k.shape[1]
    CH = chunk
    n = (Sk + CH - 1) // CH
    pad = n * CH - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, pad)))
        if k_scale is not None:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))

    def body(carry, i):
        m_prev, l_prev, acc = carry
        kb = lax.dynamic_slice_in_dim(k, i * CH, CH, axis=1)
        vb = lax.dynamic_slice_in_dim(v, i * CH, CH, axis=1)
        mb = lax.dynamic_slice_in_dim(mask, i * CH, CH, axis=2)
        if k_scale is not None:
            ks = lax.dynamic_slice_in_dim(k_scale, i * CH, CH, axis=1)
            vs = lax.dynamic_slice_in_dim(v_scale, i * CH, CH, axis=1)
            kb = (kb.astype(jnp.float32)
                  * ks[..., None].astype(jnp.float32)).astype(qg.dtype)
            vb = (vb.astype(jnp.float32)
                  * vs[..., None].astype(jnp.float32)).astype(qg.dtype)
        s = jnp.einsum("bsugh,btuh->bugst", qg, kb).astype(jnp.float32) * scale
        s = jnp.where(mb[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bugst,btuh->bugsh", p.astype(vb.dtype), vb).astype(jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, U, g, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, U, g, Sq), jnp.float32)
    a0 = jnp.zeros((B, U, g, Sq, hd), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0),
                              jnp.arange(n, dtype=jnp.int32))
    o = acc / jnp.maximum(l[..., None], 1e-30)
    return o.transpose(0, 3, 1, 2, 4).astype(qg.dtype)  # (B,Sq,U,g,hd)


def attention(p: Params, h: jax.Array, cfg: ModelConfig, plan: GQAPlan,
              ctx: ParallelCtx, *, positions: jax.Array, causal: bool = True,
              q_mask_tbl: Optional[np.ndarray] = None,
              chunk: int = 0) -> jax.Array:
    """Full-sequence attention (train / prefill).  Returns the TP-partial
    output projection; caller reduces."""
    q, k, v = _qkv_rope(p, h, cfg, plan, positions)
    with jax.named_scope("attention"):
        mask = _mask(positions, positions, causal=causal,
                     window=cfg.sliding_window)
        o = attn_core(q, k, v, mask, plan.g, chunk=chunk)
        if q_mask_tbl is not None:
            o = o * take_local(q_mask_tbl, ctx)[None, None, :, None].astype(o.dtype)
    return _o_proj(o, p["wo"])


def attention_decode(p: Params, h: jax.Array, cache: Dict[str, jax.Array],
                     cfg: ModelConfig, plan: GQAPlan, ctx: ParallelCtx, *,
                     positions: jax.Array,
                     q_mask_tbl: Optional[np.ndarray] = None,
                     chunk: Optional[int] = None, ring: bool = False,
                     project: bool = True,
                     block_tbl: Optional[jax.Array] = None,
                     kv_lens: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode step against a KV cache.

    ``project=False`` returns the pre-projection per-head output
    (B, 1, Q, hd) instead of the wo-projected TP partial — the overlapped
    decode path feeds it to :func:`repro.core.overlap.collective_matmul` so
    the output projection pipelines against its own all-reduce.

    h: (B, 1, D); cache['k']/cache['v']: (B, S_max, U, hd);
    positions: (B,) index where the new token is written.

    Variants (all selected by the cache layout itself):
    * int8 KV: cache['k'] is int8 with per-(pos, head) bf16 scales in
      cache['k_scale']/['v_scale'] — K/V are dequantized chunk-by-chunk.
    * ring buffer: ``ring=True`` with S_max == sliding_window — slot
      ``pos % W`` is overwritten and every slot is one of the last W
      positions, so the sliding-window mask degenerates to slot-validity.
    * paged: ``block_tbl`` (B, max_blocks) int32 maps logical blocks to
      physical blocks of cache['k'] (n_blocks, block_size, U, hd); the new
      token scatters through the table and each slot attends over its
      first ``kv_lens`` positions (default ``positions + 1``): on a TPU
      read in place by the paged kernel, elsewhere gathered back to the
      logical (B, max_blocks*block_size, U, hd) layout, the same math as
      the dense path on the same logical contents.
    """
    if block_tbl is not None:
        assert not ring, "paged cache is incompatible with the ring buffer"
        assert cache["k"].dtype != jnp.int8, \
            "paged cache is incompatible with int8 KV"
        return _attention_decode_paged(p, h, cache, cfg, plan, ctx,
                                       positions=positions,
                                       q_mask_tbl=q_mask_tbl, chunk=chunk,
                                       project=project, block_tbl=block_tbl,
                                       kv_lens=kv_lens)
    q, k_new, v_new = _qkv_rope(p, h, cfg, plan, positions[:, None])
    B, S_max = cache["k"].shape[0], cache["k"].shape[1]
    quant = cache["k"].dtype == jnp.int8
    write_pos = positions % S_max if ring else positions
    bidx = jnp.arange(B)
    with jax.named_scope("kv_write"):
        if quant:
            def q8(t):  # (B,1,U,hd) -> int8 payload + (B,U) scale
                tf = t[:, 0].astype(jnp.float32)
                sc = jnp.maximum(jnp.max(jnp.abs(tf), axis=-1) / 127.0,
                                 1e-30)
                qq = jnp.clip(jnp.round(tf / sc[..., None]), -127, 127)
                return qq.astype(jnp.int8), sc.astype(jnp.bfloat16)
            kq, ksc = q8(k_new)
            vq, vsc = q8(v_new)
            k = cache["k"].at[bidx, write_pos].set(kq)
            v = cache["v"].at[bidx, write_pos].set(vq)
            k_scale = cache["k_scale"].at[bidx, write_pos].set(ksc)
            v_scale = cache["v_scale"].at[bidx, write_pos].set(vsc)
        else:
            k = cache["k"].at[bidx, write_pos].set(
                k_new[:, 0].astype(cache["k"].dtype))
            v = cache["v"].at[bidx, write_pos].set(
                v_new[:, 0].astype(cache["v"].dtype))
            k_scale = v_scale = None
    with jax.named_scope("attention"):
        kpos = jnp.arange(S_max, dtype=jnp.int32)
        if ring:
            # every live slot is within the window by construction; only
            # slots not yet written (pos < W) are masked out
            mask = kpos[None, :] <= positions[:, None]
            mask = jnp.broadcast_to(mask[:, None, :], (B, 1, S_max))
        else:
            mask = _mask(positions[:, None], kpos, causal=True,
                         window=cfg.sliding_window)
        if chunk is None:
            chunk = 1024 if S_max > 8192 else 0
        o = attn_core(q, k, v, mask, plan.g, chunk=chunk, k_scale=k_scale,
                      v_scale=v_scale)
        if q_mask_tbl is not None:
            o = o * take_local(q_mask_tbl, ctx)[None, None, :, None].astype(o.dtype)
    out = _o_proj(o, p["wo"]) if project else o
    new_cache = {"k": k, "v": v}
    if quant:
        new_cache["k_scale"] = k_scale
        new_cache["v_scale"] = v_scale
    return out, new_cache


def _attention_decode_paged(p: Params, h: jax.Array,
                            cache: Dict[str, jax.Array], cfg: ModelConfig,
                            plan: GQAPlan, ctx: ParallelCtx, *,
                            positions: jax.Array, q_mask_tbl, chunk,
                            project: bool, block_tbl: jax.Array,
                            kv_lens: Optional[jax.Array]):
    """Paged one-token decode: scatter the new K/V through the block table,
    then attend over each slot's first ``kv_lens`` positions.

    On a TPU the Pallas kernel reads each slot's live blocks where they lie
    in the pool; elsewhere (the CPU) :func:`paged_attention_gathered`
    gathers the logical view and masks it, the kernel's reference.  Table
    rows of inactive slots point at the pool's reserved trash block; their
    writes land there and their reads (none, at ``kv_lens`` 0) are
    discarded by the caller, so the fixed-shape batch keeps stepping."""
    q, k_new, v_new = _qkv_rope(p, h, cfg, plan, positions[:, None])
    B = h.shape[0]
    bs_blk = cache["k"].shape[1]
    bidx = jnp.arange(B)
    with jax.named_scope("kv_write"):
        pb = block_tbl[bidx, positions // bs_blk]    # (B,) physical block
        off = positions % bs_blk
        k = cache["k"].at[pb, off].set(k_new[:, 0].astype(cache["k"].dtype))
        v = cache["v"].at[pb, off].set(v_new[:, 0].astype(cache["v"].dtype))
    if kv_lens is None:
        kv_lens = positions + 1
    window = cfg.sliding_window

    def in_place(q, k, v, tbl, pos, lens):
        with jax.named_scope("attention"):
            return paged_decode_attention(q[:, 0], k, v, tbl, lens,
                                          window=window)[:, None]

    def gathered(q, k, v, tbl, pos, lens):
        return paged_attention_gathered(q, k, v, tbl, pos, g=plan.g,
                                        window=window, chunk=chunk)

    o = lax.platform_dependent(q, k, v, block_tbl, positions, kv_lens,
                               tpu=in_place, default=gathered)
    if q_mask_tbl is not None:
        with jax.named_scope("attention"):
            o = o * take_local(q_mask_tbl, ctx)[None, None, :, None].astype(o.dtype)
    out = _o_proj(o, p["wo"]) if project else o
    return out, {"k": k, "v": v}


def paged_attention_gathered(q: jax.Array, k: jax.Array, v: jax.Array,
                             block_tbl: jax.Array, positions: jax.Array, *,
                             g: int, window: int = 0,
                             chunk: Optional[int] = None) -> jax.Array:
    """One-token attention over a paged pool through its logical view:
    gather every slot's (max_blocks*bs, U, hd) rows through the table,
    then mask past ``positions`` and attend as the dense path does.

    q: (B, 1, U*g, hd); k/v: (n_blocks, bs, U, hd); block_tbl:
    (B, max_blocks).  Returns (B, 1, U*g, hd)."""
    B = q.shape[0]
    S_max = block_tbl.shape[1] * k.shape[1]
    U, hd = k.shape[-2], k.shape[-1]
    with jax.named_scope("kv_gather"):
        k_log = k[block_tbl].reshape(B, S_max, U, hd)
        v_log = v[block_tbl].reshape(B, S_max, U, hd)
    with jax.named_scope("attention"):
        kpos = jnp.arange(S_max, dtype=jnp.int32)
        mask = _mask(positions[:, None], kpos, causal=True, window=window)
        if chunk is None:
            chunk = 1024 if S_max > 8192 else 0
        return attn_core(q, k_log, v_log, mask, g, chunk=chunk)


def attention_chunk_step(p: Params, h: jax.Array,
                         cache: Dict[str, jax.Array], cfg: ModelConfig,
                         plan: GQAPlan, ctx: ParallelCtx, *,
                         positions: jax.Array,
                         q_mask_tbl: Optional[np.ndarray] = None,
                         chunk: int = 0, project: bool = True,
                         block_tbl: Optional[jax.Array] = None,
                         slot: Optional[jax.Array] = None,
                         trash=0) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Chunked-prefill attention: C prompt tokens write into the decode
    cache, then attend causally over everything written so far.

    h: (B, C, D); positions: (B, C) int32 write positions.  ``slot`` (a
    traced scalar) admits a single request (B == 1) into one row of a
    batch-wide cache — the on-device splice the continuous batcher's
    admission step uses.  Dense and paged (``block_tbl``) layouts share the
    call; the paged path scatters/gathers through the table first, and
    ``trash`` is the pool's reserved block that pads past the logical
    capacity write to.

    Trailing pad tokens are safe *by the write-ordering invariant*: a pad at
    position p >= prompt_len writes garbage K/V, but every later read at
    decode position q only exposes kpos <= q, and position q is overwritten
    by the real decode write before any such read (see DESIGN.md §7).
    """
    q, k_new, v_new = _qkv_rope(p, h, cfg, plan, positions)
    B = h.shape[0]
    paged = block_tbl is not None
    if paged:
        assert cache["k"].dtype != jnp.int8
        bs_blk = cache["k"].shape[1]
        S_max = block_tbl.shape[1] * bs_blk
    else:
        S_max = cache["k"].shape[1]
    kd = cache["k"].dtype
    if slot is not None:
        assert B == 1, "slot admission is per-request"
        pos_row = positions[0]                       # (C,)
        if paged:
            with jax.named_scope("kv_write"):
                row = lax.dynamic_index_in_dim(block_tbl, slot, 0,
                                               keepdims=False)
                # pads beyond the logical capacity must not clamp into the
                # slot's last live block — route them to the trash block
                pb = jnp.where(pos_row < S_max, row[jnp.minimum(
                    pos_row // bs_blk, block_tbl.shape[1] - 1)], trash)
                off = pos_row % bs_blk
                k = cache["k"].at[pb, off].set(k_new[0].astype(kd))
                v = cache["v"].at[pb, off].set(v_new[0].astype(kd))
            U, hd = k.shape[-2], k.shape[-1]
            with jax.named_scope("kv_gather"):
                k_att = k[row].reshape(1, S_max, U, hd)
                v_att = v[row].reshape(1, S_max, U, hd)
        else:
            with jax.named_scope("kv_write"):
                k = cache["k"].at[slot, pos_row].set(k_new[0].astype(kd))
                v = cache["v"].at[slot, pos_row].set(v_new[0].astype(kd))
            with jax.named_scope("kv_gather"):
                k_att = lax.dynamic_index_in_dim(k, slot, 0, keepdims=True)
                v_att = lax.dynamic_index_in_dim(v, slot, 0, keepdims=True)
    else:
        bidx = jnp.arange(B)[:, None]
        if paged:
            with jax.named_scope("kv_write"):
                pb = block_tbl[bidx, jnp.minimum(positions // bs_blk,
                                                 block_tbl.shape[1] - 1)]
                pb = jnp.where(positions < S_max, pb, trash)  # (B, C)
                off = positions % bs_blk
                k = cache["k"].at[pb, off].set(k_new.astype(kd))
                v = cache["v"].at[pb, off].set(v_new.astype(kd))
            U, hd = k.shape[-2], k.shape[-1]
            with jax.named_scope("kv_gather"):
                k_att = k[block_tbl].reshape(B, S_max, U, hd)
                v_att = v[block_tbl].reshape(B, S_max, U, hd)
        else:
            with jax.named_scope("kv_write"):
                k = cache["k"].at[bidx, positions].set(k_new.astype(kd))
                v = cache["v"].at[bidx, positions].set(v_new.astype(kd))
            k_att, v_att = k, v
    with jax.named_scope("attention"):
        kpos = jnp.arange(S_max, dtype=jnp.int32)
        mask = _mask(positions, kpos, causal=True, window=cfg.sliding_window)
        o = attn_core(q, k_att, v_att, mask, plan.g, chunk=chunk)
        if q_mask_tbl is not None:
            o = o * take_local(q_mask_tbl, ctx)[None, None, :, None].astype(o.dtype)
    out = _o_proj(o, p["wo"]) if project else o
    return out, {"k": k, "v": v}


def cross_attention(p: Params, h: jax.Array, enc_k: jax.Array,
                    enc_v: jax.Array, cfg: ModelConfig, plan: GQAPlan,
                    ctx: ParallelCtx,
                    q_mask_tbl: Optional[np.ndarray] = None) -> jax.Array:
    """Decoder cross-attention over precomputed encoder K/V (no RoPE, no
    mask — whisper style)."""
    q = jnp.einsum("bsd,dqh->bsqh", h, p["wq"])
    if "bq" in p:
        q = q + p["bq"][None, None]
    Sq, Sk = h.shape[1], enc_k.shape[1]
    mask = jnp.ones((Sq, Sk), bool)
    o = attn_core(q, enc_k, enc_v, mask, plan.g,
                  chunk=1024 if Sk > 8192 else 0)
    if q_mask_tbl is not None:
        o = o * take_local(q_mask_tbl, ctx)[None, None, :, None].astype(o.dtype)
    return jnp.einsum("bsqh,qhd->bsd", o, p["wo"])


def cross_kv(p: Params, enc_out: jax.Array) -> Tuple[jax.Array, jax.Array]:
    k = jnp.einsum("btd,duh->btuh", enc_out, p["wk"])
    v = jnp.einsum("btd,duh->btuh", enc_out, p["wv"])
    if "bk" in p:
        k = k + p["bk"][None, None]
        v = v + p["bv"][None, None]
    return k, v


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.act == "swiglu":
        kg, ku, kd = split_keys(key, 3)
        return {"wg": dense_init(kg, (d, f), d, cfg.dtype),
                "wu": dense_init(ku, (d, f), d, cfg.dtype),
                "wd": dense_init(kd, (f, d), f, cfg.dtype)}
    k1, k2 = split_keys(key, 2)
    return {"w1": dense_init(k1, (d, f), d, cfg.dtype),
            "b1": jnp.zeros((f,), cfg.dtype),
            "w2": dense_init(k2, (f, d), f, cfg.dtype)}


def mlp(p: Params, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Returns TP-partial output (wd/w2 row-sharded)."""
    act = mlp_hidden(p, h, cfg)
    return jnp.einsum("bsf,fd->bsd", act, mlp_down_w(p, cfg))


def mlp_hidden(p: Params, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Up-projection + activation only: the (B, S, f_local) tensor feeding
    the row-parallel down-projection (split out so the overlapped decode
    path can fuse that GEMM with its all-reduce)."""
    if cfg.act == "swiglu":
        a = jnp.einsum("bsd,df->bsf", h, p["wg"])
        b = jnp.einsum("bsd,df->bsf", h, p["wu"])
        return jax.nn.silu(a) * b
    return jax.nn.gelu(jnp.einsum("bsd,df->bsf", h, p["w1"]) + p["b1"])


def mlp_down_w(p: Params, cfg: ModelConfig) -> jax.Array:
    """The row-sharded down-projection weight ((f_local, D), output last)."""
    return p["wd"] if cfg.act == "swiglu" else p["w2"]


# ---------------------------------------------------------------------------
# Vocab-parallel embedding / logits / loss
# ---------------------------------------------------------------------------


def init_embed(key, cfg: ModelConfig, vocab_pad: int) -> Params:
    ke, kh = split_keys(key, 2)
    p = {"tok": dense_init(ke, (vocab_pad, cfg.d_model), cfg.d_model,
                           cfg.dtype)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(kh, (cfg.d_model, vocab_pad), cfg.d_model,
                               cfg.dtype)
    return p


def embed_lookup(p: Params, ids: jax.Array, ctx: ParallelCtx,
                 vocab_pad: int, *, sp: bool = False) -> jax.Array:
    """Vocab-parallel lookup: local gather + TP reduce (paper AR site #0)."""
    with jax.named_scope("embed"):
        table = p["tok"]
        v_loc = table.shape[0]
        if v_loc == vocab_pad and not ctx.has_tp:
            return table[ids]
        start = tp_rank(ctx) * v_loc
        local = ids - start
        ok = (local >= 0) & (local < v_loc)
        x = table[jnp.clip(local, 0, v_loc - 1)]
        x = jnp.where(ok[..., None], x, 0)
        if sp:
            return hier.tp_reduce_scatter(x, ctx, dim=1)
        return hier.tp_all_reduce(x, ctx, scatter_dim=-1)


def lm_logits(p: Params, x: jax.Array) -> jax.Array:
    """Local (vocab-sharded) logits."""
    head = p["head"] if "head" in p else p["tok"].T
    return jnp.einsum("bsd,dv->bsv", x, head)


def _pmax_const(x: jax.Array, axes) -> jax.Array:
    """lax.pmax treated as a constant under AD (it has no JVP rule; the
    logsumexp max-shift never needs one)."""
    @jax.custom_jvp
    def f(v):
        return lax.pmax(v, axes)

    @f.defjvp
    def f_jvp(primals, tangents):
        (v,) = primals
        return f(v), jnp.zeros_like(v)

    return f(lax.stop_gradient(x))


def sharded_xent(logits_loc: jax.Array, labels: jax.Array,
                 ctx: ParallelCtx, vocab_pad: int, vocab_real: int,
                 mask: Optional[jax.Array] = None) -> jax.Array:
    """Cross-entropy over vocab-sharded logits without gathering the vocab.

    logits_loc: (..., V_loc) this device's slice; labels: (...) global ids.
    Returns mean loss (scalar, already averaged over batch axes).
    """
    v_loc = logits_loc.shape[-1]
    start = tp_rank(ctx) * v_loc
    lf = logits_loc.astype(jnp.float32)
    # mask vocab padding slots (global ids >= vocab_real)
    gidx = start + jnp.arange(v_loc)
    lf = jnp.where((gidx < vocab_real)[None, None, :]
                   if lf.ndim == 3 else (gidx < vocab_real), lf, NEG_INF)
    m = jnp.max(lf, axis=-1)
    if ctx.has_tp:
        m = _pmax_const(m, ctx.tp_axes)
    # standard logsumexp trick: the max shift is a constant wrt gradients
    m = lax.stop_gradient(m)
    se = jnp.sum(jnp.exp(lf - m[..., None]), axis=-1)
    local = labels - start
    ok = (local >= 0) & (local < v_loc)
    picked = jnp.take_along_axis(
        lf, jnp.clip(local, 0, v_loc - 1)[..., None], axis=-1)[..., 0]
    picked = jnp.where(ok, picked, 0.0)
    if ctx.has_tp:
        se = lax.psum(se, ctx.tp_axes)
        picked = lax.psum(picked, ctx.tp_axes)
    nll = jnp.log(se) + m - picked
    if mask is not None:
        nll = nll * mask
        denom = jnp.maximum(jnp.sum(mask), 1.0)
    else:
        denom = float(np.prod(nll.shape))
    loss = jnp.sum(nll) / denom
    return hier.dp_psum_mean(loss, ctx)


def greedy_sample(logits_loc: jax.Array, ctx: ParallelCtx,
                  vocab_real: int) -> jax.Array:
    """Greedy next-token over vocab-sharded logits: argmax via pmax+pick.
    logits_loc: (B, V_loc) -> (B,) int32 global token ids."""
    v_loc = logits_loc.shape[-1]
    start = tp_rank(ctx) * v_loc
    lf = logits_loc.astype(jnp.float32)
    gidx = start + jnp.arange(v_loc)
    lf = jnp.where(gidx[None, :] < vocab_real, lf, NEG_INF)
    loc_best = jnp.argmax(lf, axis=-1)
    loc_max = jnp.take_along_axis(lf, loc_best[:, None], axis=-1)[:, 0]
    if not ctx.has_tp:
        return loc_best.astype(jnp.int32)
    gmax = lax.pmax(loc_max, ctx.tp_axes)
    # Prefer the lowest global id among ties.
    cand = jnp.where(loc_max >= gmax, start + loc_best, jnp.int32(2**30))
    return lax.pmin(cand.astype(jnp.int32), ctx.tp_axes)


def sample_token(logits: jax.Array, rng: jax.Array, *,
                 temperature: float = 1.0, top_k: int = 0,
                 vocab_real: Optional[int] = None) -> jax.Array:
    """Temperature / top-k sampling over FULL (unsharded) logits.

    logits: (B, V); returns (B,) int32.  temperature=0 -> greedy.
    (The sharded serving path gathers logits first via sample=False on the
    decode builder; vocab padding slots are masked here.)
    """
    lf = logits.astype(jnp.float32)
    if vocab_real is not None and vocab_real < lf.shape[-1]:
        mask = jnp.arange(lf.shape[-1]) < vocab_real
        lf = jnp.where(mask[None, :], lf, NEG_INF)
    if temperature <= 0.0:
        return jnp.argmax(lf, axis=-1).astype(jnp.int32)
    lf = lf / temperature
    if top_k > 0 and top_k < lf.shape[-1]:
        kth = jnp.sort(lf, axis=-1)[:, -top_k][:, None]
        lf = jnp.where(lf >= kth, lf, NEG_INF)
    return jax.random.categorical(rng, lf, axis=-1).astype(jnp.int32)


__all__ = [
    "rms_norm", "layer_norm", "apply_norm", "init_norm", "rope_tables",
    "apply_rope", "init_attention", "attention", "attention_decode",
    "attention_chunk_step",
    "cross_attention", "cross_kv", "attn_core", "init_mlp", "mlp",
    "mlp_hidden", "mlp_down_w",
    "init_embed", "embed_lookup", "lm_logits", "sharded_xent",
    "greedy_sample", "sample_token", "tp_rank", "take_local", "NEG_INF",
]
