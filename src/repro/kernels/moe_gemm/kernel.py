"""Fused grouped expert FFN (gated-SiLU) for the MoE dispatch path.

One kernel computes, per local expert e and token tile c:

    out[e, c] = (silu(x[e,c] @ wg[e]) * (x[e,c] @ wu[e])) @ wd[e]

Tiling: grid (E_loc, C/BC, F/BF) with the expert-hidden dim innermost
("arbitrary") so the (BC, D) f32 accumulator persists in VMEM across F
tiles — the gate/up/down chain never round-trips through HBM, which is the
fusion XLA cannot do across the dispatch buffers.  BC=BF=128 keeps every
matmul MXU-shaped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _moe_ffn_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref):
    i_f = pl.program_id(2)
    n_f = pl.num_programs(2)

    @pl.when(i_f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)       # (BC, D)
    wg = wg_ref[0].astype(jnp.float32)     # (D, BF)
    wu = wu_ref[0].astype(jnp.float32)
    wd = wd_ref[0].astype(jnp.float32)     # (BF, D)
    g = jax.lax.dot(x, wg, preferred_element_type=jnp.float32)
    u = jax.lax.dot(x, wu, preferred_element_type=jnp.float32)
    h = jax.nn.silu(g) * u                 # (BC, BF)
    acc_ref[...] += jax.lax.dot(h, wd, preferred_element_type=jnp.float32)

    @pl.when(i_f == n_f - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f",
                                             "interpret"))
def moe_expert_ffn_call(x, wg, wu, wd, *, block_c: int = 128,
                        block_f: int = 128, interpret=False):
    """x: (E, C, D); wg/wu: (E, D, F); wd: (E, F, D) -> (E, C, D).
    C % block_c == 0, F % block_f == 0 (ops.py pads)."""
    E, C, D = x.shape
    F = wg.shape[-1]
    grid = (E, C // block_c, F // block_f)
    return pl.pallas_call(
        _moe_ffn_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, D), lambda e, ic, i_f: (e, ic, 0)),
            pl.BlockSpec((1, D, block_f), lambda e, ic, i_f: (e, 0, i_f)),
            pl.BlockSpec((1, D, block_f), lambda e, ic, i_f: (e, 0, i_f)),
            pl.BlockSpec((1, block_f, D), lambda e, ic, i_f: (e, i_f, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c, D),
                               lambda e, ic, i_f: (e, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, wg, wu, wd)
