"""Mamba selective-scan as a Pallas TPU kernel.

Recurrence per channel c and state s:

    h_t = exp(A[c,s] * dt[t,c]) h_{t-1} + dt[t,c] * x[t,c] * B[t,s]
    y_t[c] = sum_s h_t[c,s] * C[t,s]

Tiling: grid (B, Ci/BC, T/CT), time innermost/sequential; the recurrent
state h (BC, S) lives in f32 VMEM scratch.  Within a time chunk the kernel
walks CT steps with a fori_loop of (BC, S) VPU ops — channels are the 128-
lane dimension, the small state dim (16) rides in sublanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref,
                y_ref, hout_ref, h_ref, *, chunk_t: int):
    it = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(it == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)            # (BC, S)

    def step(t, carry):
        h = carry
        dt_t = dt_ref[0, t, :].astype(jnp.float32)       # (BC,)
        x_t = x_ref[0, t, :].astype(jnp.float32)         # (BC,)
        b_t = b_ref[0, t, :].astype(jnp.float32)         # (S,)
        c_t = c_ref[0, t, :].astype(jnp.float32)         # (S,)
        decay = jnp.exp(a * dt_t[:, None])               # (BC, S)
        h = decay * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_t = jnp.sum(h * c_t[None, :], axis=1)          # (BC,)
        y_ref[0, t, :] = y_t.astype(y_ref.dtype)
        return h

    h = lax.fori_loop(0, chunk_t, step, h_ref[...])
    h_ref[...] = h

    @pl.when(it == nt - 1)
    def _final():
        hout_ref[0] = h.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk_t", "block_c",
                                             "interpret"))
def ssm_scan_call(x, dt, b, c, a, h0, *, chunk_t: int = 64,
                  block_c: int = 128, interpret=False):
    """x/dt: (B, T, Ci); b/c: (B, T, S); a: (Ci, S); h0: (B, Ci, S) f32.
    T % chunk_t == 0, Ci % block_c == 0.
    Returns (y (B,T,Ci) f32, h_fin (B,Ci,S) f32)."""
    B, T, Ci = x.shape
    S = b.shape[-1]
    grid = (B, Ci // block_c, T // chunk_t)
    xspec = pl.BlockSpec((1, chunk_t, block_c),
                         lambda ib, ic, it: (ib, it, ic))
    bspec = pl.BlockSpec((1, chunk_t, S), lambda ib, ic, it: (ib, it, 0))
    return pl.pallas_call(
        functools.partial(_ssm_kernel, chunk_t=chunk_t),
        grid=grid,
        in_specs=[xspec, xspec, bspec, bspec,
                  pl.BlockSpec((block_c, S), lambda ib, ic, it: (ic, 0)),
                  pl.BlockSpec((1, block_c, S),
                               lambda ib, ic, it: (ib, ic, 0))],
        out_specs=[xspec,
                   pl.BlockSpec((1, block_c, S),
                                lambda ib, ic, it: (ib, ic, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, T, Ci), jnp.float32),
                   jax.ShapeDtypeStruct((B, Ci, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_c, S), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, b, c, a, h0)
