"""Blocked causal flash attention (prefill) for TPU.

Tiling: grid (B, Hq, Sq/BQ, Skv/BK) with the KV dimension innermost
("arbitrary" semantics) so the f32 accumulator scratch persists across KV
blocks — the online-softmax state never leaves VMEM.  Q/K tiles are
(BQ|BK, head_dim) with BQ=BK=128 by default: MXU-shaped (128x128) matmuls.

GQA is handled in the index maps: query head h reads kv head h // q_per_kv
— no materialized KV expansion.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1.0e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, kv_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = iq * block_q + lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 0)
    k_pos = ik * block_k + lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 1)
    mask = k_pos < kv_len
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window

    # Skip fully-masked KV blocks (causal upper triangle / outside window).
    run = ik >= 0
    if causal:
        run &= ik * block_k <= iq * block_q + block_q - 1
    if window > 0:
        run &= ik * block_k + block_k - 1 > iq * block_q - window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (BQ, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (BK, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "q_per_kv", "kv_len",
    "interpret"))
def flash_attention_call(q, k, v, *, causal: bool, window: int,
                         q_per_kv: int, kv_len: int,
                         block_q: int = 128, block_k: int = 128,
                         interpret=False):
    """q: (B, Hq, Sq, hd); k/v: (B, Hkv, Skv, hd), Sq/Skv pre-padded to
    block multiples.  Returns (B, Hq, Sq, hd)."""
    B, Hq, Sq, hd = q.shape
    Skv = k.shape[2]
    scale = hd ** -0.5
    grid = (B, Hq, Sq // block_q, Skv // block_k)
    kern = functools.partial(_flash_kernel, scale=scale, causal=causal,
                             window=window, block_q=block_q,
                             block_k=block_k, kv_len=kv_len)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, iq, ik, qpk=q_per_kv:
                         (b, h // qpk, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, iq, ik, qpk=q_per_kv:
                         (b, h // qpk, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
