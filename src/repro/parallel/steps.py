"""Step builders: shard_map'd train / prefill / decode step functions.

These close over an :class:`ArchPlan`, a :class:`ParallelCtx` and a mesh, and
return jit-ready functions together with their in/out shardings — consumed by
the launcher, the dry-run, the inference engine and the tests alike.

Training step = FSDP(all-gather weights per layer) x TP x SP x grad-accum
microbatches x remat, with the cross-pod gradient reduction performed by the
paper's recursive-doubling strategy (optionally int8-compressed).

Decode step = Megatron-style TP with the per-layer all-reduce strategy under
study (flat | hier_ring | hier_rd | hier_rd_halving).

Serving-stack builders (cache init / admission / fused serve / spec verify
/ prefill-only / KV splice) share two conventions: ``mesh=None`` returns a
plain jit-able callable over the LOCAL ctx while a mesh returns the
shard_map'd production step (one engine, two deployments), and every
builder captures its ``ar_table`` at build time (``autotune.using``) so
``ar_strategy="auto"`` call sites resolve against the right table even
when jit defers tracing — in disaggregated serving the prefill and decode
pools' builders therefore dispatch against *different* tables.  The same
scope also resolves ``ctx.seq_parallel="auto"``: prefill-shaped builders
(full prefill / admission / chunked admission / prefill-only) ask the
captured tuner whether their residual message size warrants the
sequence-parallel RS+AG layout, while decode builders never decompose
(DESIGN.md §10).

Invariants the serve-side steps rely on (details in ``inference.kv_cache``
and DESIGN.md §7-§9): stale-slot / pad / rejected-draft K/V writes are
harmless (trash-routed on the paged path, write-order-covered on the dense
path), and a paged cache's block table rides outside the layer scan.
Known gaps: chunked admission, spec verify, and the disaggregation steps
are dense-family-only; serve steps cannot shard slots over dp axes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from ..core.pcontext import ParallelCtx, LOCAL
from ..core import autotune
from ..core import hierarchical as hier
from ..models.transformer import (ArchPlan, forward_lm, decode_step,
                                  ef_sites_for, init_cache, prefill_chunk,
                                  seed_cache)
from ..models import layers as L
from ..training.optimizer import (adamw_init, adamw_update, cosine_lr,
                                  global_grad_norm)
from . import sharding as shd


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _all_axes(ctx: ParallelCtx):
    seen, out = set(), []
    for a in ctx.tp_slow + ctx.tp_fast + ctx.dp + ctx.fsdp:
        if a not in seen:
            seen.add(a)
            out.append(a)
    return tuple(out)


def _repl_factors(params, specs, mesh):
    """How many devices hold each leaf's shard (for norm accounting)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    total = int(np.prod(mesh.devices.shape))

    def f(_, spec):
        shard_ways = 1
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                shard_ways *= sizes[a]
        return total // shard_ways

    return jax.tree.map(f, params, specs)


@dataclasses.dataclass
class BuiltStep:
    fn: Callable            # the jit-able python callable (shard_map'd)
    in_specs: Any
    out_specs: Any
    mesh: Any
    ctx: ParallelCtx
    donate_argnums: Tuple[int, ...] = ()

    def jit(self, **kw):
        kw.setdefault("donate_argnums", self.donate_argnums)
        return jax.jit(self.fn, **kw)


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def build_train_step(ap: ArchPlan, ctx: ParallelCtx, mesh, *,
                     microbatches: int = 1, scan_layers: bool = True,
                     remat: bool = True, sp: bool = True,
                     base_lr: float = 3e-4, warmup: int = 100,
                     total_steps: int = 10000, clip_norm: float = 1.0,
                     frame_embeds: bool = False, patch_embeds: bool = False
                     ) -> BuiltStep:
    cfg = ap.cfg
    sp = sp and cfg.family not in ("ssm", "hybrid")  # recurrences need full seq
    pod_axes = tuple(a for a in ctx.dp if a not in ctx.fsdp)

    # All specs are computed from a ShapeDtypeStruct template — no arrays
    # are materialized here.
    from ..models.transformer import init_params  # local import

    template = jax.eval_shape(lambda k: init_params(k, ap),
                              jax.random.PRNGKey(0))
    pspecs = shd.param_specs(template, ctx, mesh, fsdp=True)
    fdims = shd.param_fsdp_dims(template, ctx, mesh)
    repl = _repl_factors(template, pspecs, mesh)
    all_axes = _all_axes(ctx)

    fdims_blocks = fdims["blocks"]
    layer_map = (lambda bp: shd.gather_params(bp, fdims_blocks, ctx)) \
        if ctx.fsdp else None
    enc_layer_map = None
    if ctx.fsdp and "enc_blocks" in fdims:
        fdims_enc = fdims["enc_blocks"]
        enc_layer_map = lambda bp: shd.gather_params(bp, fdims_enc, ctx)

    def loss_fn(params, tokens, labels, extra):
        logits, aux, _, _ = forward_lm(
            params, tokens, ap, ctx, sp=sp, scan_layers=scan_layers,
            layer_map=layer_map, enc_layer_map=enc_layer_map, remat=remat,
            frame_embeds=extra.get("frames"),
            patch_embeds=extra.get("patches"))
        # data pipeline provides labels already shifted/aligned per position
        loss = L.sharded_xent(logits, labels, ctx, ap.vocab_pad,
                              cfg.vocab_size)
        if cfg.is_moe:
            loss = loss + cfg.router_aux_coef * hier.dp_psum_mean(aux, ctx)
        return loss

    def train_step(params, opt_state, batch):
        # Gather non-block params once (embed / final norms).
        def full_params(p):
            if not ctx.fsdp:
                return p
            out = dict(p)
            for k in p:
                if k in ("blocks", "enc_blocks"):
                    continue
                out[k] = shd.gather_params(p[k], fdims[k], ctx)
            return out

        tokens, labels = batch["tokens"], batch["labels"]
        B_loc = tokens.shape[0]
        mb = microbatches
        assert B_loc % mb == 0, (B_loc, mb)
        tok_mb = tokens.reshape(mb, B_loc // mb, -1)
        lab_mb = labels.reshape(mb, B_loc // mb, -1)
        extras = {}
        for k2, name in (("frames", "frames"), ("patches", "patches")):
            if name in batch:
                e = batch[name]
                extras[k2] = e.reshape((mb, B_loc // mb) + e.shape[1:])

        def micro(grads_acc, xs):
            tok, lab = xs[0], xs[1]
            extra = {}
            i = 2
            if "frames" in extras:
                extra["frames"] = xs[i]; i += 1
            if "patches" in extras:
                extra["patches"] = xs[i]; i += 1
            l, g = jax.value_and_grad(
                lambda p: loss_fn(full_params(p), tok, lab, extra))(params)
            grads_acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), grads_acc, g)
            return grads_acc, l

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        xs = (tok_mb, lab_mb)
        if "frames" in extras:
            xs = xs + (extras["frames"],)
        if "patches" in extras:
            xs = xs + (extras["patches"],)
        grads, losses = lax.scan(micro, g0, xs)
        grads = jax.tree.map(lambda g: g / mb, grads)
        loss = jnp.mean(losses)

        # Cross-pod / replicated-leaf gradient reduction (paper technique).
        # FSDP-gathered leaves (fd >= 0) are already reduce-scattered over
        # ctx.fsdp by AD; only the pod (slow-DCN) sum remains — the paper's
        # inter-node recursive-doubling phase.  Leaves replicated across
        # FSDP still need the sum over every dp axis.
        def finish(g, fd):
            if fd >= 0:
                return hier.grad_cross_pod_reduce(g, ctx, pod_axes) \
                    if pod_axes else g
            fast_dp = tuple(a for a in ctx.dp if a not in pod_axes)
            if fast_dp:
                g = lax.psum(g, fast_dp)
            return hier.grad_cross_pod_reduce(g, ctx, pod_axes) \
                if pod_axes else g

        grads = jax.tree.map(finish, grads, fdims)
        gnorm = global_grad_norm(grads, repl, all_axes)
        skip = ~jnp.isfinite(gnorm)
        scale = jnp.where(gnorm > clip_norm, clip_norm / (gnorm + 1e-9), 1.0)
        lr = cosine_lr(opt_state["step"], base_lr=base_lr, warmup=warmup,
                       total=total_steps)
        new_params, new_opt = adamw_update(grads, opt_state, params, lr=lr,
                                           grad_scale=scale, skip=skip)
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "skipped": skip.astype(jnp.float32), "lr": lr}
        return new_params, new_opt, metrics

    data_spec = {"tokens": shd.data_specs(ctx, ndim=2),
                 "labels": shd.data_specs(ctx, ndim=2)}
    if frame_embeds:
        data_spec["frames"] = shd.data_specs(ctx, ndim=3)
    if patch_embeds:
        data_spec["patches"] = shd.data_specs(ctx, ndim=3)
    opt_spec = {"m": pspecs, "v": pspecs, "step": P()}
    in_specs = (pspecs, opt_spec, data_spec)
    out_specs = (pspecs, opt_spec,
                 {"loss": P(), "grad_norm": P(), "skipped": P(), "lr": P()})
    fn = shard_map(train_step, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return BuiltStep(fn=fn, in_specs=in_specs, out_specs=out_specs,
                     mesh=mesh, ctx=ctx, donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# Serve steps (prefill + decode)
# ---------------------------------------------------------------------------


def build_decode_step(ap: ArchPlan, ctx: ParallelCtx, mesh, *,
                      scan_layers: bool = True, fsdp_serve: bool = False,
                      sample: bool = True, attn_chunk=None,
                      kv_quant: bool = False, weight_quant: bool = False,
                      window_cache: bool = False,
                      ar_table: Optional[str] = None) -> BuiltStep:
    """One-token decode across the batch: (params, cache, tokens, positions)
    -> (next_tokens | logits, new_cache).

    ``ar_table``: path to a persisted autotune table (JSON).  The tuner is
    captured at build time and activated around the step body during
    tracing, so every ``ar_strategy="auto"`` call site in THIS step
    resolves against THIS table even if another build installs a different
    one before jit traces (falls back to the analytic seed, or the
    ``REPRO_AR_TABLE`` env var, when None/missing)."""
    cfg = ap.cfg
    ar_tuner = autotune.tuner_for(ar_table)
    from ..models.transformer import init_params

    serve_ctx = ctx if fsdp_serve else ctx.replace(fsdp=())
    template = jax.eval_shape(lambda k: init_params(k, ap),
                              jax.random.PRNGKey(0))
    if weight_quant:
        from .quant import quantize_params, dequant_layer
        template = jax.eval_shape(quantize_params, template)
    pspecs = shd.param_specs(template, serve_ctx, mesh, fsdp=fsdp_serve)
    fdims = shd.param_fsdp_dims(template, serve_ctx, mesh) if fsdp_serve \
        else None
    layer_map = None
    if fsdp_serve:
        layer_map = lambda bp: shd.gather_params(bp, fdims["blocks"],
                                                 serve_ctx)
    if weight_quant:
        from .quant import dequant_layer
        _g = layer_map
        layer_map = (lambda bp: dequant_layer(_g(bp))) if _g \
            else dequant_layer

    def step(params, cache, tokens, positions):
        if fsdp_serve:
            full = dict(params)
            for k in params:
                if k not in ("blocks", "enc_blocks"):
                    full[k] = shd.gather_params(params[k], fdims[k],
                                                serve_ctx)
            params = full
        with autotune.using(ar_tuner):  # trace-time 'auto' dispatch
            logits, new_cache = decode_step(params, cache, tokens,
                                            positions, ap, serve_ctx,
                                            scan_layers=scan_layers,
                                            layer_map=layer_map,
                                            attn_chunk=attn_chunk,
                                            kv_ring=window_cache)
        if sample:
            out = L.greedy_sample(logits, serve_ctx, cfg.vocab_size)
        else:
            out = lax.all_gather(logits, serve_ctx.tp_axes, axis=1,
                                 tiled=True) if serve_ctx.has_tp else logits
        return out, new_cache

    cache_t = jax.eval_shape(lambda: init_cache(
        ap, 1, 8, local=False, kv_quant=kv_quant,
        window_cache=window_cache,
        ef_sites=ef_sites_for(serve_ctx, cfg)))
    cspecs = shd.cache_spec(cache_t, serve_ctx)
    dp = serve_ctx.dp
    dspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    in_specs = (pspecs, cspecs, P(dspec), P(dspec))
    out_specs = (P(dspec) if sample else P(dspec, None), cspecs)
    fn = shard_map(step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=False)
    return BuiltStep(fn=fn, in_specs=in_specs, out_specs=out_specs,
                     mesh=mesh, ctx=serve_ctx, donate_argnums=(1,))


def build_prefill(ap: ArchPlan, ctx: ParallelCtx, mesh, *,
                  scan_layers: bool = True, s_max: int,
                  fsdp_serve: bool = False, attn_chunk=None,
                  sp: Optional[bool] = None,
                  frame_embeds: bool = False, patch_embeds: bool = False,
                  ar_table: Optional[str] = None) -> BuiltStep:
    """Prefill: run the full prompt, return (first_token, cache).
    ``ar_table`` as in :func:`build_decode_step`.  ``sp=None`` resolves
    the sequence-parallel residual layout from ``ctx.seq_parallel`` per
    prompt length (an explicit bool forces it)."""
    cfg = ap.cfg
    ar_tuner = autotune.tuner_for(ar_table)
    from ..models.transformer import init_params

    serve_ctx = ctx if fsdp_serve else ctx.replace(fsdp=())
    template = jax.eval_shape(lambda k: init_params(k, ap),
                              jax.random.PRNGKey(0))
    pspecs = shd.param_specs(template, serve_ctx, mesh, fsdp=fsdp_serve)
    fdims = shd.param_fsdp_dims(template, serve_ctx, mesh) if fsdp_serve \
        else None
    layer_map = (lambda bp: shd.gather_params(bp, fdims["blocks"], serve_ctx)) \
        if fsdp_serve else None

    def prefill(params, tokens, *extra):
        if fsdp_serve:
            full = dict(params)
            for k in params:
                if k not in ("blocks", "enc_blocks"):
                    full[k] = shd.gather_params(params[k], fdims[k],
                                                serve_ctx)
            params = full
        kw = {}
        i = 0
        if frame_embeds:
            kw["frame_embeds"] = extra[i]; i += 1
        if patch_embeds:
            kw["patch_embeds"] = extra[i]; i += 1
        B, S = tokens.shape
        chunk = attn_chunk if attn_chunk is not None \
            else (1024 if S > 8192 else 0)
        with autotune.using(ar_tuner):  # trace-time 'auto' dispatch
            logits, _, states, enc_out = forward_lm(
                params, tokens, ap, serve_ctx, sp=sp,
                scan_layers=scan_layers, collect_state=True,
                layer_map=layer_map, chunk=chunk, **kw)
        cache = init_cache(ap, B, s_max, local=True,
                           ef_sites=ef_sites_for(serve_ctx, cfg))
        enc_kv = None
        if cfg.enc_layers:
            def xkv(bp):
                # xattn is never FSDP-sharded (see sharding._leaf_plan)
                return L.cross_kv(bp["xattn"], enc_out)
            enc_kv = jax.vmap(xkv)(params["blocks"])
        cache = seed_cache(cache, states, enc_kv=enc_kv)
        nxt = L.greedy_sample(logits[:, -1], serve_ctx, cfg.vocab_size)
        return nxt, cache

    cache_t = jax.eval_shape(lambda: init_cache(
        ap, 1, 8, local=False, ef_sites=ef_sites_for(serve_ctx, cfg)))
    cspecs = shd.cache_spec(cache_t, serve_ctx)
    dp = serve_ctx.dp
    dspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    in_sp = [pspecs, P(dspec, None)]
    if frame_embeds:
        in_sp.append(P(dspec, None, None))
    if patch_embeds:
        in_sp.append(P(dspec, None, None))
    in_specs = tuple(in_sp)
    out_specs = (P(dspec), cspecs)
    fn = shard_map(prefill, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return BuiltStep(fn=fn, in_specs=in_specs, out_specs=out_specs,
                     mesh=mesh, ctx=serve_ctx)


# ---------------------------------------------------------------------------
# Serving-stack steps (continuous batching: cache init / admission / serve)
# ---------------------------------------------------------------------------
#
# These power ``inference.scheduler.ContinuousBatcher`` on *either* path:
# with ``mesh=None`` they return plain jit-able callables over the LOCAL ctx
# (single device), with a mesh they return shard_map'd steps that inherit
# the ar_table / overlap_matmul wiring of the decode builder above — one
# serving engine, two deployments.


def _serve_ctx(ctx: ParallelCtx, mesh, fsdp_serve: bool) -> ParallelCtx:
    if mesh is None:
        return LOCAL
    return ctx if fsdp_serve else ctx.replace(fsdp=())


def _serve_params(ap: ArchPlan, serve_ctx, mesh, fsdp_serve):
    """(pspecs, fdims, layer_map, full_params) for the serve-side builders."""
    from ..models.transformer import init_params
    if mesh is None:
        return None, None, None, lambda p: p
    template = jax.eval_shape(lambda k: init_params(k, ap),
                              jax.random.PRNGKey(0))
    pspecs = shd.param_specs(template, serve_ctx, mesh, fsdp=fsdp_serve)
    if not fsdp_serve:
        return pspecs, None, None, lambda p: p
    fdims = shd.param_fsdp_dims(template, serve_ctx, mesh)
    layer_map = lambda bp: shd.gather_params(bp, fdims["blocks"], serve_ctx)

    def full_params(params):
        full = dict(params)
        for k in params:
            if k not in ("blocks", "enc_blocks"):
                full[k] = shd.gather_params(params[k], fdims[k], serve_ctx)
        return full

    return pspecs, fdims, layer_map, full_params


def local_sharding(mesh, device=None):
    """Where a mesh-less replica's arrays live: ``device`` (the first
    device when None).  None with a mesh, whose steps place their own."""
    if mesh is not None:
        return None
    return SingleDeviceSharding(
        jax.devices()[0] if device is None else device)


def serve_param_shardings(ap: ArchPlan, ctx: ParallelCtx, mesh, *,
                          fsdp_serve: bool = False, device=None):
    """Where each parameter leaf of a serving replica lives: the serve
    steps' param specs on ``mesh``, or the one ``device`` of a mesh-less
    replica (one sharding for the whole tree).  Used as ``out_shardings``
    of the weight init and as the target of ``jax.device_put`` for weights
    a caller hands in, so a step never re-lays its weights out per call."""
    if mesh is None:
        return local_sharding(None, device)
    pspecs = _serve_params(ap, _serve_ctx(ctx, mesh, fsdp_serve), mesh,
                           fsdp_serve)[0]
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def _full_vocab(logits, serve_ctx: ParallelCtx):
    """Gather vocab-sharded logits (vocab last) back to the full vocab —
    the one shared gather every sampled path routes through."""
    if not serve_ctx.has_tp:
        return logits
    return lax.all_gather(logits, serve_ctx.tp_axes,
                          axis=logits.ndim - 1, tiled=True)


def _sample_next(logits, serve_ctx: ParallelCtx, cfg, rng,
                 temperature: float, top_k: int):
    """Next-token sampling over (possibly vocab-sharded) logits, on device.
    temperature=0 -> sharded greedy argmax; otherwise gather the vocab and
    run layers.sample_token (temperature / top-k)."""
    if temperature > 0.0:
        return L.sample_token(_full_vocab(logits, serve_ctx), rng,
                              temperature=temperature,
                              top_k=top_k, vocab_real=cfg.vocab_size)
    return L.greedy_sample(logits, serve_ctx, cfg.vocab_size)


def _finite_slots(logits, serve_ctx: ParallelCtx):
    """Per-slot all-finite flag over (possibly vocab-sharded) logits.

    ``logits``: (..., slots leading, vocab last).  Non-finite counts are
    psum'd over the TP axes so the flag is *replicated* across shards —
    a NaN on any vocab shard marks the slot on every device (relying on
    NaN propagation to make shards agree independently would be
    replication-unsound).  This is the device half of the batcher's
    quarantine guard (DESIGN.md §11): detection happens where the
    corruption lives, the host only reads one bool per slot.
    """
    bad = (~jnp.isfinite(logits.astype(jnp.float32)))
    bad = bad.sum(axis=tuple(range(1, bad.ndim))).astype(jnp.int32)
    if serve_ctx.has_tp:
        bad = lax.psum(bad, serve_ctx.tp_axes)
    return bad == 0


def _sample_next_slots(logits, serve_ctx: ParallelCtx, cfg, keys, idx,
                       temperature: float, top_k: int):
    """Per-slot next-token sampling for the fused serve step.

    Slot ``s`` draws with the *stateless* key ``fold_in(keys[s], idx[s])``
    — the request's own sampling chain (``scheduler.request_sampling_key``),
    independent of the global step schedule and of which other slots are
    active.  That schedule-independence is what makes sampled
    (temperature > 0) disaggregated streams token-identical to colocated
    serving and preemption recomputes resample their original tokens.
    temperature=0 -> sharded greedy argmax (keys untouched).
    """
    if temperature <= 0.0:
        return L.greedy_sample(logits, serve_ctx, cfg.vocab_size)
    full = _full_vocab(logits, serve_ctx)
    subs = jax.vmap(jax.random.fold_in)(keys, idx)
    return jax.vmap(
        lambda row, k2: L.sample_token(row[None], k2,
                                       temperature=temperature,
                                       top_k=top_k,
                                       vocab_real=cfg.vocab_size)[0]
    )(full, subs)


def build_cache_init(ap: ArchPlan, ctx: ParallelCtx, mesh, *, slots: int,
                     s_max: int, block_size: int = 0,
                     n_blocks: Optional[int] = None,
                     kv_quant: bool = False,
                     fsdp_serve: bool = False) -> BuiltStep:
    """() -> zeroed decode cache for ``slots`` batch rows (paged when
    block_size > 0, int8 K/V + scales when ``kv_quant``), created
    shard-local under the mesh."""
    serve_ctx = _serve_ctx(ctx, mesh, fsdp_serve)
    ef_sites = ef_sites_for(serve_ctx, ap.cfg)

    def init():
        return init_cache(ap, slots, s_max, local=True,
                          block_size=block_size, n_blocks=n_blocks,
                          kv_quant=kv_quant, ef_sites=ef_sites)

    if mesh is None:
        return BuiltStep(fn=init, in_specs=(), out_specs=None, mesh=None,
                         ctx=serve_ctx)
    cache_t = jax.eval_shape(lambda: init_cache(
        ap, slots, s_max, local=False, block_size=block_size,
        n_blocks=n_blocks, kv_quant=kv_quant, ef_sites=ef_sites))
    cspecs = shd.cache_spec(cache_t, serve_ctx)
    fn = shard_map(init, mesh=mesh, in_specs=(), out_specs=cspecs,
                   check_vma=False)
    return BuiltStep(fn=fn, in_specs=(), out_specs=cspecs, mesh=mesh,
                     ctx=serve_ctx)


def build_serve_step(ap: ArchPlan, ctx: ParallelCtx, mesh, *, s_max: int,
                     scan_layers: bool = True, fsdp_serve: bool = False,
                     temperature: float = 0.0, top_k: int = 0,
                     block_size: int = 0, n_blocks: Optional[int] = None,
                     slots: int = 1, attn_chunk=None,
                     kv_quant: bool = False,
                     ar_table: Optional[str] = None) -> BuiltStep:
    """Fused continuous-batching step: decode all slots + sample + advance
    the device-side slot state.

    (params, cache, state) -> (emitted, done, finite, state', cache')
    with state = {tokens, positions, remaining: (slots,) i32, active:
    (slots,) bool, rng: (slots, 2) u32 per-request sampling-chain base
    keys, sample_idx: (slots,) i32 tokens sampled so far}.  ``finite``
    is the per-slot all-finite-logits flag (``_finite_slots``): a False
    entry means the slot's token this step is garbage — the batcher
    quarantines the slot and recomputes it (DESIGN.md §11) instead of
    emitting.  Slot ``s`` samples
    with ``fold_in(rng[s], sample_idx[s])`` — the request's own chain, so
    sampled streams are schedule-independent (see ``_sample_next_slots``).
    Inactive slots keep decoding into their own (dense) row or the
    trash block (paged), and a paged cache reads none of their blocks —
    no masking in the hot path; ``emitted`` holds the
    sampled token where active, the stale token elsewhere, and ``done``
    flags slots that finished this step (caller frees/refills them).
    ``ar_table`` / ``ctx.overlap_matmul`` behave as in build_decode_step.
    """
    cfg = ap.cfg
    ar_tuner = autotune.tuner_for(ar_table)
    serve_ctx = _serve_ctx(ctx, mesh, fsdp_serve)
    if mesh is not None and serve_ctx.dp:
        raise ValueError("serve step cannot shard slots over dp axes; "
                         "run one batcher per data-parallel replica")
    pspecs, _, layer_map, full_params = _serve_params(ap, serve_ctx, mesh,
                                                      fsdp_serve)

    def step(params, cache, state):
        params = full_params(params)
        active = state["active"]
        with autotune.using(ar_tuner):
            logits, new_cache = decode_step(
                params, cache, state["tokens"], state["positions"], ap,
                serve_ctx, scan_layers=scan_layers, layer_map=layer_map,
                attn_chunk=attn_chunk,
                kv_lens=jnp.where(active, state["positions"] + 1, 0))
        with jax.named_scope("sample"):
            nxt = _sample_next_slots(logits, serve_ctx, cfg, state["rng"],
                                     state["sample_idx"], temperature, top_k)
            finite = _finite_slots(logits, serve_ctx)
            emitted = jnp.where(active, nxt, state["tokens"])
            act_i = active.astype(jnp.int32)
            positions = state["positions"] + act_i
            remaining = state["remaining"] - act_i
            done = active & ((remaining <= 0) | (positions >= s_max - 1))
            state2 = {"tokens": emitted, "positions": positions,
                      "remaining": remaining, "active": active & ~done,
                      "rng": state["rng"],
                      "sample_idx": state["sample_idx"] + act_i}
        return emitted, done, finite, state2, new_cache

    if mesh is None:
        return BuiltStep(fn=step, in_specs=None, out_specs=None, mesh=None,
                         ctx=serve_ctx, donate_argnums=(1, 2))
    cache_t = jax.eval_shape(lambda: init_cache(
        ap, slots, s_max, local=False, block_size=block_size,
        n_blocks=n_blocks, kv_quant=kv_quant,
        ef_sites=ef_sites_for(serve_ctx, ap.cfg)))
    cspecs = shd.cache_spec(cache_t, serve_ctx)
    sspec = {"tokens": P(None), "positions": P(None),
             "remaining": P(None), "active": P(None),
             "rng": P(None, None), "sample_idx": P(None)}
    in_specs = (pspecs, cspecs, sspec)
    out_specs = (P(None), P(None), P(None), sspec, cspecs)
    fn = shard_map(step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=False)
    return BuiltStep(fn=fn, in_specs=in_specs, out_specs=out_specs,
                     mesh=mesh, ctx=serve_ctx, donate_argnums=(1, 2))


def _spec_targets(logits, drafts, serve_ctx: ParallelCtx, cfg, rng,
                  temperature: float, top_k: int):
    """Per-position target tokens + draft accept mask for the verify pass.

    logits: (B, C, V_loc) vocab-sharded scores for the C = k+1 chunk
    inputs; drafts: (B, k) proposed tokens.  Returns (tgt (B, C) int32,
    match (B, k) bool) where ``tgt[:, j]`` is the target-model token
    emitted at draft position j when j is the first rejection (or the
    bonus position j == k), and ``match[:, j]`` accepts draft j.

    Greedy (temperature == 0): tgt is the sharded argmax and a draft
    matches iff it equals it — the emitted stream is bitwise the plain
    greedy stream.  Sampled: drafts are deterministic proposals (every
    shipped drafter is), so exact speculative rejection sampling reduces
    to accept draft d with probability p(d), else resample from the
    renormalized leftover p with d zeroed — per-token output distribution
    is exactly the target p (see DESIGN.md §8).
    """
    B, C = logits.shape[0], logits.shape[1]
    k = C - 1
    if temperature <= 0.0:
        flat = logits.reshape(B * C, logits.shape[-1])
        tgt = L.greedy_sample(flat, serve_ctx, cfg.vocab_size)
        tgt = tgt.reshape(B, C)
        return tgt, drafts == tgt[:, :k]
    lf = _full_vocab(logits, serve_ctx).astype(jnp.float32)
    V = lf.shape[-1]
    lf = jnp.where((jnp.arange(V) < cfg.vocab_size)[None, None, :], lf,
                   L.NEG_INF)
    lf = lf / temperature
    if top_k > 0 and top_k < V:
        kth = jnp.sort(lf, axis=-1)[..., -top_k][..., None]
        lf = jnp.where(lf >= kth, lf, L.NEG_INF)
    p = jax.nn.softmax(lf, axis=-1)                       # (B, C, V)
    r_acc, r_res, r_bonus = jax.random.split(rng, 3)
    p_draft = jnp.take_along_axis(p[:, :k], drafts[..., None],
                                  axis=-1)[..., 0]        # (B, k)
    match = jax.random.uniform(r_acc, (B, k)) < p_draft
    # residual resample: zero the rejected draft, renormalize -> exactly p
    onehot = jax.nn.one_hot(drafts, V, dtype=bool)
    resid = jnp.where(onehot, L.NEG_INF, lf[:, :k])
    corr = jax.random.categorical(r_res, resid, axis=-1)  # (B, k)
    bonus = jax.random.categorical(r_bonus, lf[:, k], axis=-1)
    tgt = jnp.concatenate([corr, bonus[:, None]],
                          axis=1).astype(jnp.int32)
    return tgt, match


def build_spec_verify_step(ap: ArchPlan, ctx: ParallelCtx, mesh, *, k: int,
                           s_max: int, slots: int = 1,
                           scan_layers: bool = True,
                           fsdp_serve: bool = False,
                           temperature: float = 0.0, top_k: int = 0,
                           block_size: int = 0,
                           n_blocks: Optional[int] = None,
                           attn_chunk: int = 0,
                           kv_quant: bool = False,
                           ar_table: Optional[str] = None) -> BuiltStep:
    """Speculative-decoding verify step: score ``k`` drafted tokens for
    every slot in ONE fused pass over the chunked-prefill machinery.

    (params, cache, state, drafts (slots, k), rng) ->
    (emitted (slots, k+1), accepted (slots,) i32, finite (slots,) bool,
    cache').  ``finite`` flags slots whose verify logits were all finite
    (``_finite_slots``); a False slot's emitted/accepted are garbage and
    the batcher quarantines it (DESIGN.md §11).

    The chunk input for each slot is ``[state.tokens, drafts]`` (C = k+1
    tokens) written/attended at positions ``state.positions + [0..k]`` —
    exactly the K/V writes sequential decode would perform if every draft
    were accepted.  ``accepted`` is the longest verified draft prefix;
    the caller takes ``emitted[:, :accepted+1]`` (accepted drafts + one
    correction/bonus token) and rolls the rejected tail's K/V back
    (``BlockAllocator.truncate`` on the paged path; on the dense path the
    stale tail is overwritten before any read by the same write-ordering
    invariant that covers chunk padding).

    The per-layer all-reduces of this step carry C-times-wider messages
    than one-token decode, so with ``ar_strategy="auto"`` the captured
    ``ar_table`` re-dispatches every call site on the new sizes — the
    workload-side shift into the paper's strategy-sensitive regime.

    Dense (attention-only) families only, like ``prefill_chunk``.
    """
    cfg = ap.cfg
    if cfg.family != "dense":
        raise ValueError("speculative verify rides the chunked-prefill "
                         f"path: dense families only, not {cfg.family!r}")
    if kv_quant:
        raise ValueError("spec verify rides prefill_chunk, which cannot "
                         "re-read an int8 KV cache mid-chunk: kv_quant "
                         "is incompatible with speculative decoding")
    if k < 1:
        raise ValueError(f"spec k must be >= 1, got {k}")
    C = k + 1
    ar_tuner = autotune.tuner_for(ar_table)
    serve_ctx = _serve_ctx(ctx, mesh, fsdp_serve)
    if mesh is not None and serve_ctx.dp:
        raise ValueError("spec verify step cannot shard slots over dp "
                         "axes; run one batcher per replica")
    pspecs, _, layer_map, full_params = _serve_params(ap, serve_ctx, mesh,
                                                      fsdp_serve)

    def verify(params, cache, state, drafts, rng):
        params = full_params(params)
        tokens, positions = state["tokens"], state["positions"]
        x = jnp.concatenate([tokens[:, None], drafts], axis=1)   # (B, C)
        pos = positions[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        with autotune.using(ar_tuner):
            logits, cache2 = prefill_chunk(
                params, cache, x, pos, ap, serve_ctx,
                scan_layers=scan_layers, layer_map=layer_map,
                attn_chunk=attn_chunk, return_logits=True)
        finite = _finite_slots(logits, serve_ctx)
        tgt, match = _spec_targets(logits, drafts, serve_ctx, cfg, rng,
                                   temperature, top_k)
        prefix = jnp.cumprod(match.astype(jnp.int32), axis=1)    # (B, k)
        accepted = prefix.sum(axis=1)                            # in [0, k]
        idx = jnp.arange(C, dtype=jnp.int32)[None, :]
        drafts_pad = jnp.pad(drafts, ((0, 0), (0, 1)))
        # j < accepted: the (verified) draft; j == accepted: correction or
        # bonus.  Greedy drafts equal tgt where accepted, so either branch
        # is the plain greedy token there.
        emitted = jnp.where(idx < accepted[:, None], drafts_pad, tgt)
        return emitted, accepted.astype(jnp.int32), finite, cache2

    if mesh is None:
        return BuiltStep(fn=verify, in_specs=None, out_specs=None,
                         mesh=None, ctx=serve_ctx, donate_argnums=(1,))
    cache_t = jax.eval_shape(lambda: init_cache(
        ap, slots, s_max, local=False, block_size=block_size,
        n_blocks=n_blocks, ef_sites=ef_sites_for(serve_ctx, ap.cfg)))
    cspecs = shd.cache_spec(cache_t, serve_ctx)
    sspec = {"tokens": P(None), "positions": P(None),
             "remaining": P(None), "active": P(None)}
    in_specs = (pspecs, cspecs, sspec, P(None, None), P(None))
    out_specs = (P(None, None), P(None), P(None), cspecs)
    fn = shard_map(verify, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return BuiltStep(fn=fn, in_specs=in_specs, out_specs=out_specs,
                     mesh=mesh, ctx=serve_ctx, donate_argnums=(1,))


def build_prefill_only_step(ap: ArchPlan, ctx: ParallelCtx, mesh, *,
                            prompt_len: int, scan_layers: bool = True,
                            fsdp_serve: bool = False,
                            temperature: float = 0.0, top_k: int = 0,
                            ar_table=None) -> BuiltStep:
    """Prefill-pool step for disaggregated serving: run one request's
    prompt, sample the first token, and hand the per-layer K/V states
    straight back — no decode loop, no persistent serving cache.

    (params, prompt (1, prompt_len), rng) -> (first_token (1,),
    k (L, 1, prompt_len, U, hd), v (same)).

    The returned states are the raw material of the KV handoff
    (``inference.kv_cache.export-from-states`` -> :class:`KVBundle` ->
    decode-pool splice); on a mesh the kv-slot dim comes back TP-gathered
    per ``sharding.kv_states_spec`` so the host sees the full global slot
    layout.  ``prompt_len`` is static — one executable per distinct
    length, cached by the pool (the chunked-admission path avoids the
    recompiles; dense families only either way).  Because this step runs
    on the *prefill pool's* mesh with its own ``ar_table``, its per-layer
    all-reduces dispatch on prompt-sized messages — the bandwidth-bound
    end of the paper's strategy crossover — independent of the decode
    pool's operating point.
    """
    cfg = ap.cfg
    if cfg.family != "dense":
        raise ValueError("disaggregated prefill is attention-only: dense "
                         f"families only, not {cfg.family!r}")
    ar_tuner = autotune.tuner_for(ar_table)
    serve_ctx = _serve_ctx(ctx, mesh, fsdp_serve)
    pspecs, _, layer_map, full_params = _serve_params(ap, serve_ctx, mesh,
                                                      fsdp_serve)

    def prefill_only(params, prompt, rng):
        params = full_params(params)
        with autotune.using(ar_tuner):
            logits, _, states, _ = forward_lm(
                params, prompt, ap, serve_ctx, scan_layers=scan_layers,
                collect_state=True, layer_map=layer_map,
                chunk=1024 if prompt_len > 8192 else 0)
        nxt = _sample_next(logits[:, -1], serve_ctx, cfg, rng,
                           temperature, top_k)
        return nxt, states["k"], states["v"]

    if mesh is None:
        return BuiltStep(fn=prefill_only, in_specs=None, out_specs=None,
                         mesh=None, ctx=serve_ctx)
    kv_spec = shd.kv_states_spec(serve_ctx)
    in_specs = (pspecs, P(None, None), P(None))
    out_specs = (P(None), kv_spec, kv_spec)
    fn = shard_map(prefill_only, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return BuiltStep(fn=fn, in_specs=in_specs, out_specs=out_specs,
                     mesh=mesh, ctx=serve_ctx)


def build_kv_splice_step(ap: ArchPlan, ctx: ParallelCtx, mesh, *,
                         n_tokens: int, s_max: int, slots: int = 1,
                         block_size: int = 0,
                         n_blocks: Optional[int] = None,
                         fsdp_serve: bool = False) -> BuiltStep:
    """Decode-pool import of a KV handoff: splice one request's K/V states
    into cache row ``slot`` on device.

    (cache, k (L, 1, n_tokens, U, hd), v, slot) -> cache'.

    The inbound states must already be in THIS pool's global slot layout
    (``kv_cache.heads_to_slots`` re-expands the canonical bundle); the
    splice itself is the shared ``seed_cache`` path, so dense targets take
    a ``dynamic_update_slice`` and paged targets scatter through the
    block table — the caller must have grown the slot's block list to
    cover ``[0, n_tokens + 1)`` first (the +1 covers the first decode
    write, same as admission).  ``n_tokens`` is static: one executable
    per distinct handoff length, cached by the batcher.
    """
    del n_tokens  # static via the bundle's shape; named for the cache key
    serve_ctx = _serve_ctx(ctx, mesh, fsdp_serve)

    def splice(cache, k, v, slot):
        return seed_cache(cache, {"k": k, "v": v}, slot=slot)

    if mesh is None:
        return BuiltStep(fn=splice, in_specs=None, out_specs=None,
                         mesh=None, ctx=serve_ctx, donate_argnums=(0,))
    cache_t = jax.eval_shape(lambda: init_cache(
        ap, slots, s_max, local=False, block_size=block_size,
        n_blocks=n_blocks, ef_sites=ef_sites_for(serve_ctx, ap.cfg)))
    cspecs = shd.cache_spec(cache_t, serve_ctx)
    kv_spec = shd.kv_states_spec(serve_ctx)
    in_specs = (cspecs, kv_spec, kv_spec, P())
    fn = shard_map(splice, mesh=mesh, in_specs=in_specs, out_specs=cspecs,
                   check_vma=False)
    return BuiltStep(fn=fn, in_specs=in_specs, out_specs=cspecs, mesh=mesh,
                     ctx=serve_ctx, donate_argnums=(0,))


def build_admit_step(ap: ArchPlan, ctx: ParallelCtx, mesh, *, s_max: int,
                     prompt_len: int, slots: int = 1,
                     scan_layers: bool = True, fsdp_serve: bool = False,
                     temperature: float = 0.0, top_k: int = 0,
                     block_size: int = 0, n_blocks: Optional[int] = None,
                     kv_quant: bool = False,
                     ar_table: Optional[str] = None) -> BuiltStep:
    """Full-prefill admission: run one request's prompt, splice its KV /
    recurrent states into cache row ``slot`` on device, sample the first
    token.  (params, cache, prompt (1, prompt_len), slot, rng) ->
    (first_token (1,), cache').

    ``prompt_len`` is static — one executable per distinct length, cached
    by the batcher.  Length-bucketing via padding is NOT safe here in
    general (recurrent states advance over pads, MoE routing capacity is
    load-dependent), which is exactly why this path exists for every
    family; attention-only (dense) families should use
    :func:`build_admit_chunk_step` instead to avoid per-length recompiles.
    """
    cfg = ap.cfg
    ar_tuner = autotune.tuner_for(ar_table)
    serve_ctx = _serve_ctx(ctx, mesh, fsdp_serve)
    pspecs, _, layer_map, full_params = _serve_params(ap, serve_ctx, mesh,
                                                      fsdp_serve)

    def admit(params, cache, prompt, slot, rng):
        params = full_params(params)
        with autotune.using(ar_tuner):
            logits, _, states, _ = forward_lm(
                params, prompt, ap, serve_ctx, scan_layers=scan_layers,
                collect_state=True, layer_map=layer_map,
                chunk=1024 if prompt_len > 8192 else 0)
        cache2 = seed_cache(cache, states, slot=slot)
        nxt = _sample_next(logits[:, -1], serve_ctx, cfg, rng,
                           temperature, top_k)
        return nxt, cache2

    if mesh is None:
        return BuiltStep(fn=admit, in_specs=None, out_specs=None,
                         mesh=None, ctx=serve_ctx, donate_argnums=(1,))
    cache_t = jax.eval_shape(lambda: init_cache(
        ap, slots, s_max, local=False, block_size=block_size,
        n_blocks=n_blocks, kv_quant=kv_quant,
        ef_sites=ef_sites_for(serve_ctx, ap.cfg)))
    cspecs = shd.cache_spec(cache_t, serve_ctx)
    in_specs = (pspecs, cspecs, P(None, None), P(), P(None))
    out_specs = (P(None), cspecs)
    fn = shard_map(admit, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return BuiltStep(fn=fn, in_specs=in_specs, out_specs=out_specs,
                     mesh=mesh, ctx=serve_ctx, donate_argnums=(1,))


def build_admit_chunk_step(ap: ArchPlan, ctx: ParallelCtx, mesh, *,
                           chunk: int, s_max: int, slots: int = 1,
                           scan_layers: bool = True,
                           fsdp_serve: bool = False,
                           temperature: float = 0.0, top_k: int = 0,
                           block_size: int = 0,
                           n_blocks: Optional[int] = None,
                           sample: bool = True,
                           kv_quant: bool = False,
                           ar_table: Optional[str] = None) -> BuiltStep:
    """Chunked-prefill admission: feed the prompt through in fixed-size
    chunks of ``chunk`` tokens, writing K/V into cache row ``slot`` as it
    goes — one executable for every prompt length (trailing chunk is
    padded; see layers.attention_chunk_step for why pads are safe).

    With ``sample=True`` (the *final*-chunk executable):
    (params, cache, tokens (1, chunk), positions (1, chunk), slot,
    last_idx, rng) -> (token (1,), cache') — the sampled continuation of
    the token at in-chunk index ``last_idx``.  With ``sample=False`` (the
    intermediate-chunk executable) the vocab head, sampling, and their TP
    collectives are skipped entirely and the step returns just ``cache'``.
    Dense families only (see transformer.prefill_chunk).
    """
    cfg = ap.cfg
    if kv_quant:
        raise ValueError("chunked admission rides prefill_chunk, which "
                         "cannot re-read an int8 KV cache mid-prompt: "
                         "kv_quant needs full-prefill admission")
    ar_tuner = autotune.tuner_for(ar_table)
    serve_ctx = _serve_ctx(ctx, mesh, fsdp_serve)
    pspecs, _, layer_map, full_params = _serve_params(ap, serve_ctx, mesh,
                                                      fsdp_serve)

    def admit_chunk(params, cache, tokens, positions, slot, last_idx, rng):
        params = full_params(params)
        with autotune.using(ar_tuner):
            logits, cache2 = prefill_chunk(
                params, cache, tokens, positions, ap, serve_ctx,
                scan_layers=scan_layers, layer_map=layer_map, slot=slot,
                return_logits=sample)
        if not sample:
            return cache2
        with jax.named_scope("sample"):
            last = lax.dynamic_index_in_dim(logits, last_idx, 1,
                                            keepdims=False)   # (1, V_loc)
            nxt = _sample_next(last, serve_ctx, cfg, rng, temperature, top_k)
        return nxt, cache2

    if mesh is None:
        return BuiltStep(fn=admit_chunk, in_specs=None, out_specs=None,
                         mesh=None, ctx=serve_ctx, donate_argnums=(1,))
    cache_t = jax.eval_shape(lambda: init_cache(
        ap, slots, s_max, local=False, block_size=block_size,
        n_blocks=n_blocks, ef_sites=ef_sites_for(serve_ctx, ap.cfg)))
    cspecs = shd.cache_spec(cache_t, serve_ctx)
    in_specs = (pspecs, cspecs, P(None, None), P(None, None), P(), P(),
                P(None))
    out_specs = (P(None), cspecs) if sample else cspecs
    fn = shard_map(admit_chunk, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return BuiltStep(fn=fn, in_specs=in_specs, out_specs=out_specs,
                     mesh=mesh, ctx=serve_ctx, donate_argnums=(1,))


__all__ = ["build_train_step", "build_decode_step", "build_prefill",
           "local_sharding", "serve_param_shardings", "build_cache_init",
           "build_serve_step", "build_admit_step", "build_admit_chunk_step",
           "build_spec_verify_step", "build_prefill_only_step",
           "build_kv_splice_step", "BuiltStep"]
