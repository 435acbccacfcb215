"""ShapeDtypeStruct stand-ins for every (arch x shape) dry-run cell.

No device allocation ever happens here: params, optimizer state, caches and
batches are all ShapeDtypeStructs carrying NamedShardings, so
``jit(step).lower(...)`` sees the exact production layouts.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import get_config, SHAPES, cell_plan, CellPlan
from ..core.pcontext import ParallelCtx
from ..models.transformer import (ArchPlan, make_plan, init_params,
                                  init_cache, ef_sites_for)
from ..parallel import steps as st
from ..training.optimizer import adamw_init
from .mesh import make_ctx, tp_size


def _sds(tree, specs, mesh):
    def f(leaf, spec):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, spec))
    return jax.tree.map(f, tree, specs)


@dataclasses.dataclass
class Cell:
    arch: str
    shape_name: str
    plan: CellPlan
    ap: ArchPlan
    ctx: ParallelCtx
    built: st.BuiltStep
    args: Tuple[Any, ...]          # ShapeDtypeStructs with shardings

    def lower(self):
        return self.built.jit().lower(*self.args)


def build_cell(arch: str, shape_name: str, mesh, *,
               ar_strategy: str = "flat", scan_layers: bool = True,
               cross_pod_tp: bool = False,
               cfg_override=None, extra_ctx: Optional[dict] = None,
               shape_override=None,
               kv_quant: bool = False, window_kv: bool = False,
               weight_quant: bool = False,
               fsdp_serve_override=None, sp_prefill: bool = False) -> Cell:
    """Construct the step + input specs for one dry-run cell."""
    cfg = cfg_override or get_config(arch)
    plan = cell_plan(arch, shape_name)
    shape = shape_override or plan.shape
    ctx = make_ctx(mesh, ar_strategy=ar_strategy,
                   cross_pod_tp=cross_pod_tp,
                   batch_replicated=plan.batch_replicated,
                   **(extra_ctx or {}))
    tp = tp_size(mesh, ctx)
    ap = make_plan(cfg, tp)

    params_t = jax.eval_shape(lambda k: init_params(k, ap),
                              jax.random.PRNGKey(0))

    if shape.kind == "train":
        built = st.build_train_step(
            ap, ctx, mesh,
            microbatches=plan.microbatches,
            scan_layers=scan_layers,
            frame_embeds=cfg.family == "encdec",
            patch_embeds=cfg.family == "vlm")
        opt_t = jax.eval_shape(lambda: adamw_init(params_t))
        batch_t = {
            "tokens": jax.ShapeDtypeStruct((shape.global_batch,
                                            shape.seq_len), jnp.int32),
            "labels": jax.ShapeDtypeStruct((shape.global_batch,
                                            shape.seq_len), jnp.int32),
        }
        if cfg.family == "encdec":
            batch_t["frames"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.enc_seq, cfg.d_model), cfg.dtype)
        if cfg.family == "vlm":
            batch_t["patches"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.n_patches, cfg.d_model), cfg.dtype)
        ps, os_, bs = built.in_specs
        args = (_sds(params_t, ps, mesh), _sds(opt_t, os_, mesh),
                _sds(batch_t, bs, mesh))
        return Cell(arch, shape_name, plan, ap, ctx, built, args)

    if shape.kind == "prefill":
        built = st.build_prefill(
            ap, ctx, mesh, s_max=shape.seq_len + 64,
            scan_layers=scan_layers,
            fsdp_serve=plan.fsdp_serve if fsdp_serve_override is None
            else fsdp_serve_override,
            sp=sp_prefill,
            frame_embeds=cfg.family == "encdec",
            patch_embeds=cfg.family == "vlm")
        tok_t = jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len),
                                     jnp.int32)
        arg_ts = [params_t, tok_t]
        if cfg.family == "encdec":
            arg_ts.append(jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.enc_seq, cfg.d_model), cfg.dtype))
        if cfg.family == "vlm":
            arg_ts.append(jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.n_patches, cfg.d_model), cfg.dtype))
        args = tuple(_sds(t, s, mesh)
                     for t, s in zip(arg_ts, built.in_specs))
        return Cell(arch, shape_name, plan, ap, ctx, built, args)

    # decode (decode_32k / long_500k): one new token against a seq_len cache
    window_cache = window_kv and cfg.sliding_window > 0
    built = st.build_decode_step(ap, ctx, mesh,
                                 scan_layers=scan_layers,
                                 fsdp_serve=plan.fsdp_serve
                                 if fsdp_serve_override is None
                                 else fsdp_serve_override,
                                 kv_quant=kv_quant,
                                 weight_quant=weight_quant,
                                 window_cache=window_cache)
    if weight_quant:
        from ..parallel.quant import quantize_params
        params_t = jax.eval_shape(quantize_params, params_t)
    cache_t = jax.eval_shape(
        lambda: init_cache(ap, shape.global_batch, shape.seq_len,
                           local=False, kv_quant=kv_quant,
                           window_cache=window_cache,
                           ef_sites=ef_sites_for(built.ctx, cfg)))
    tok_t = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
    pos_t = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
    ps, cs, ts, pss = built.in_specs
    args = (_sds(params_t, ps, mesh), _sds(cache_t, cs, mesh),
            _sds(tok_t, ts, mesh), _sds(pos_t, pss, mesh))
    return Cell(arch, shape_name, plan, ap, ctx, built, args)


def input_specs(arch: str, shape_name: str, mesh, **kw):
    """The task-mandated entry point: ShapeDtypeStruct stand-ins for every
    model input of this cell (weak-type-correct, shardable, no allocation)."""
    return build_cell(arch, shape_name, mesh, **kw).args


__all__ = ["build_cell", "input_specs", "Cell"]
