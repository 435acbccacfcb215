"""RWKV6 time-mix recurrence as a chunked Pallas TPU kernel.

Per (batch, head), the data-dependent-decay linear-attention recurrence

    y_t = r_t . (S_{t-1} + diag(u) k_t v_t^T);  S_t = diag(w_t) S_{t-1} + k_t v_t^T

is evaluated chunk-parallel: within a chunk of C=64 steps everything is
(C x hd) / (hd x hd) matmuls (MXU-shaped for hd=64), and the cross-chunk
state S lives in f32 VMEM scratch across the sequential chunk grid
dimension.  This is the TPU analogue of flash-linear-attention's chunked
form; the step-exact oracle lives in ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                 y_ref, sout_ref, s_ref, *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, :, 0].astype(jnp.float32)     # (C, hd)
    k = k_ref[0, :, 0].astype(jnp.float32)
    v = v_ref[0, :, 0].astype(jnp.float32)
    logw = w_ref[0, :, 0].astype(jnp.float32)  # log decay, < 0
    u = u_ref[0].astype(jnp.float32)           # (hd,)

    L = jnp.cumsum(logw, axis=0)               # inclusive
    Lm1 = L - logw                             # exclusive
    s = s_ref[...]                             # (hd_k, hd_v)

    rdec = r * jnp.exp(Lm1)
    y = jax.lax.dot(rdec, s, preferred_element_type=jnp.float32)
    kdec = k * jnp.exp(jnp.minimum(-L, 60.0))
    scores = jax.lax.dot_general(rdec, kdec, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    c = logw.shape[0]
    ti = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    si = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    scores = jnp.where(si < ti, scores, 0.0)
    y = y + jax.lax.dot(scores, v, preferred_element_type=jnp.float32)
    y = y + jnp.sum(r * u[None, :] * k, axis=1, keepdims=True) * v

    Lc = L[-1:, :]                             # (1, hd)
    kfac = k * jnp.exp(Lc - L)
    s_new = jnp.exp(Lc[0])[:, None] * s + jax.lax.dot_general(
        kfac, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_ref[...] = s_new
    y_ref[0, :, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _final():
        sout_ref[0, 0] = s_new.astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan_call(r, k, v, logw, u, s0, *, chunk: int = 64,
                    interpret=False):
    """r/k/v/logw: (B, T, H, hd) with T % chunk == 0; u: (H, hd);
    s0: (B, H, hd, hd) f32.  Returns (y (B,T,H,hd) f32, s_fin)."""
    B, T, H, hd = r.shape
    grid = (B, H, T // chunk)
    io_spec = pl.BlockSpec((1, chunk, 1, hd),
                           lambda b, h, ic: (b, ic, h, 0))
    return pl.pallas_call(
        functools.partial(_rwkv_kernel, chunk=chunk),
        grid=grid,
        in_specs=[io_spec, io_spec, io_spec, io_spec,
                  pl.BlockSpec((1, hd), lambda b, h, ic: (h, 0)),
                  pl.BlockSpec((1, 1, hd, hd),
                               lambda b, h, ic: (b, h, 0, 0))],
        out_specs=[io_spec,
                   pl.BlockSpec((1, 1, hd, hd),
                                lambda b, h, ic: (b, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, T, H, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u, s0)
