"""Pipeline parallelism (the paper's HP baseline: TP intra-node x PP
inter-node), as a real shard_map GPipe schedule.

Layers are sharded over the stage axis (the leading stacked-layer dim of the
block params), activations travel between stages via ``lax.ppermute``, and a
microbatch pipeline fills/drains over ``M + P - 1`` ticks.  TP composes
inside each stage through the same ParallelCtx collectives as everywhere
else.

Used by the TP-vs-HP comparison tests and (in alpha-beta form) by the
strong-scaling benchmarks; decode-side HP is intentionally modelled rather
than run (the paper's Obs. 2: it cannot shrink decode GEMMs — our Table 4
benchmark shows why).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map
from ..core.pcontext import ParallelCtx
from ..core import hierarchical as hier
from ..models.transformer import ArchPlan, block_forward
from ..models import layers as L
from . import sharding as shd


def build_pp_forward(ap: ArchPlan, ctx: ParallelCtx, mesh, *,
                     stage_axis: str, microbatches: int):
    """Forward pass -> vocab-sharded logits, pipelined over ``stage_axis``.

    Requirements: cfg.n_layers % n_stages == 0; global batch % microbatches
    == 0.  Embedding/head run on every stage (cheap, replicated math) but
    only stage 0's embed output and the last stage's logits are live.
    """
    cfg = ap.cfg
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_stages = sizes[stage_axis]
    assert cfg.n_layers % n_stages == 0
    layers_per_stage = cfg.n_layers // n_stages

    from ..models.transformer import init_params
    template = jax.eval_shape(lambda k: init_params(k, ap),
                              jax.random.PRNGKey(0))
    pspecs = shd.param_specs(template, ctx, mesh, fsdp=False)

    # blocks additionally shard their leading layer dim over the stage axis
    def stage_spec(spec):
        return P(*((stage_axis,) + tuple(spec)[1:]))

    pspecs = dict(pspecs)
    pspecs["blocks"] = jax.tree.map(stage_spec, pspecs["blocks"])

    def fwd(params, tokens):
        stage = lax.axis_index(stage_axis)
        B, S = tokens.shape
        mb = microbatches
        mb_sz = B // mb
        x_all = L.embed_lookup(params["embed"], tokens, ctx, ap.vocab_pad)
        x_mbs = x_all.reshape(mb, mb_sz, S, -1)
        positions = jnp.arange(S, dtype=jnp.int32)

        def run_stage(x):
            def body(x, bp):
                x, _, _ = block_forward(bp, x, ap, ctx,
                                        positions=positions, sp=False,
                                        causal=True)
                return x, None
            x, _ = lax.scan(body, x, params["blocks"])
            return x

        n_ticks = mb + n_stages - 1
        buf = jnp.zeros((mb_sz, S, x_all.shape[-1]), x_all.dtype)
        out = jnp.zeros_like(x_mbs)

        def tick(carry, t):
            buf, out = carry
            # stage 0 ingests microbatch t (when valid)
            take = jnp.clip(t, 0, mb - 1)
            injected = lax.dynamic_index_in_dim(x_mbs, take, axis=0,
                                                keepdims=False)
            buf = jnp.where((stage == 0) & (t < mb), injected, buf)
            buf = run_stage(buf)
            # collect the last stage's finished microbatch t-(P-1)
            done_idx = jnp.clip(t - (n_stages - 1), 0, mb - 1)
            is_done = (stage == n_stages - 1) & (t >= n_stages - 1)
            out = lax.cond(
                is_done,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, buf, done_idx, axis=0),
                lambda o: o, out)
            # shift activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = lax.ppermute(buf, stage_axis, perm)
            return (buf, out), None

        (buf, out), _ = lax.scan(tick, (buf, out),
                                 jnp.arange(n_ticks, dtype=jnp.int32))
        # broadcast the final stage's collected outputs to all stages
        out = lax.psum(jnp.where(stage == n_stages - 1, out,
                                 jnp.zeros_like(out)), stage_axis)
        x_full = out.reshape(B, S, -1)
        x_full = L.apply_norm(x_full, params["final_norm"], cfg)
        return L.lm_logits(params["embed"], x_full)

    tp = ctx.tp_slow + ctx.tp_fast
    vspec = tp if len(tp) > 1 else (tp[0] if tp else None)
    in_specs = (pspecs, P(None, None))
    out_specs = P(None, None, vspec)   # logits stay vocab-sharded over TP
    fn = shard_map(fwd, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=False)
    return fn, pspecs


__all__ = ["build_pp_forward"]
