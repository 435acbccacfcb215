"""Model assembly: blocks per family, LM forward, decode step, caches.

The same code path serves every assigned architecture:

==========  ================================================================
dense       llama3.2-1b, codeqwen1.5-7b, qwen1.5-32b, mistral-large-123b
moe         dbrx-132b (16e top-4), qwen3-moe-30b-a3b (128e top-8)
hybrid      hymba-1.5b (parallel attention + mamba heads per block)
ssm         rwkv6-7b (attention-free time-mix/channel-mix)
encdec      whisper-medium (stub conv frontend -> encoder -> causal decoder
            with cross-attention)
vlm         pixtral-12b (stub ViT frontend -> dense decoder; patch embeddings
            overwrite the first n_patches positions)
==========  ================================================================

All functions run single-device (LOCAL ctx) or inside shard_map; layer
weights are stacked along a leading layer axis so the stack can be scanned
(`scan_layers=True`, small compiled HLO + realistic memory analysis) or
unrolled (`scan_layers=False`, exact `cost_analysis` FLOP counting for the
roofline).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.pcontext import ParallelCtx
from ..core import hierarchical as hier
from ..core import overlap as ov
from .common import ModelConfig, GQAPlan, plan_gqa, pad_to, split_keys
from . import layers as L
from . import moe as M
from . import ssm as S
from . import rwkv as R

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Architecture plan (static per (config, tp))
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ArchPlan:
    cfg: ModelConfig
    tp: int
    gqa: Optional[GQAPlan]
    vocab_pad: int

    @property
    def q_mask_tbl(self) -> Optional[np.ndarray]:
        if self.gqa is None:
            return None
        m = self.gqa.q_mask().reshape(self.tp, self.gqa.q_slots_local)
        if m.min() >= 1.0:
            return None  # no dead slots, skip the mask multiply
        return m

    @property
    def d_ff_local(self) -> int:
        return self.cfg.d_ff // self.tp

    @property
    def d_inner_local(self) -> int:
        return self.cfg.d_inner // self.tp

    @property
    def rwkv_heads_local(self) -> int:
        return self.cfg.d_model // self.cfg.rwkv_head_dim // self.tp

    def flops_overhead(self) -> float:
        return self.gqa.flops_overhead if self.gqa else 1.0


def make_plan(cfg: ModelConfig, tp: int) -> ArchPlan:
    gqa = None
    if not cfg.attn_free:
        gqa = plan_gqa(cfg.n_heads, cfg.n_kv_heads, tp)
    for dim, name in ((cfg.d_model, "d_model"), (cfg.d_ff, "d_ff")):
        if cfg.family != "moe" and dim % tp:
            raise ValueError(f"{cfg.name}: {name}={dim} not divisible by tp={tp}")
    return ArchPlan(cfg=cfg, tp=tp, gqa=gqa, vocab_pad=pad_to(cfg.vocab_size, tp))


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def _init_block(key, ap: ArchPlan) -> Params:
    cfg = ap.cfg
    ks = split_keys(key, 8)
    if cfg.family == "ssm":
        return {"ln1": L.init_norm(cfg), "tm": R.init_rwkv_time_mix(ks[0], cfg),
                "ln2": L.init_norm(cfg), "cm": R.init_rwkv_channel_mix(ks[1], cfg)}
    p: Params = {"ln1": L.init_norm(cfg),
                 "attn": L.init_attention(ks[0], cfg, ap.gqa),
                 "ln2": L.init_norm(cfg)}
    if cfg.family == "hybrid":
        p["ssm"] = S.init_ssm(ks[1], cfg)
        p["beta"] = jnp.ones((2,), jnp.float32)
        p["mlp"] = L.init_mlp(ks[2], cfg)
    elif cfg.is_moe:
        p["moe"] = M.init_moe(ks[1], cfg)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg)
    if cfg.enc_layers:  # whisper decoder block: add cross-attention
        p["ln_x"] = L.init_norm(cfg)
        p["xattn"] = L.init_attention(ks[3], cfg, ap.gqa)
    return p


def _init_enc_block(key, ap: ArchPlan) -> Params:
    cfg = ap.cfg
    k1, k2 = split_keys(key, 2)
    return {"ln1": L.init_norm(cfg), "attn": L.init_attention(k1, cfg, ap.gqa),
            "ln2": L.init_norm(cfg), "mlp": L.init_mlp(k2, cfg)}


def _stack(blocks):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


@functools.partial(jax.jit, static_argnames="ap")
def init_params(key, ap: ArchPlan) -> Params:
    """Random weights for ``ap``'s model, layers stacked on a leading axis.

    Each stack is built by ``lax.map`` over the layer keys: one layer at a
    time is written into its slot, so no second copy of the stack is ever
    held.  Compiled once per plan; wrap it in ``jax.jit(...,
    out_shardings=...)`` (``parallel.steps.serve_param_shardings``) to
    create every leaf where it lives."""
    cfg = ap.cfg
    keys = jax.random.split(key, cfg.n_layers + cfg.enc_layers + 2)
    off = 1 + cfg.n_layers
    p: Params = {
        "embed": L.init_embed(keys[0], cfg, ap.vocab_pad),
        "blocks": lax.map(lambda k: _init_block(k, ap), keys[1:off]),
        "final_norm": L.init_norm(cfg),
    }
    if cfg.enc_layers:
        p["enc_blocks"] = lax.map(lambda k: _init_enc_block(k, ap),
                                  keys[off:off + cfg.enc_layers])
        p["enc_norm"] = L.init_norm(cfg)
    return p


# ---------------------------------------------------------------------------
# Block forward (full sequence: train / prefill)
# ---------------------------------------------------------------------------


def _seq_parallel_active(ctx: ParallelCtx, cfg: ModelConfig,
                         n_tokens: int, seq_len: int,
                         explicit: Optional[bool]) -> bool:
    """Trace-time resolution of the sequence-parallel residual layout.

    ``explicit`` (a caller's ``sp=`` argument, e.g. the training step's)
    overrides the ``ctx.seq_parallel`` knob.  Either way SP only engages
    when fast TP axes exist and divide the sequence (psum_scatter tiling
    needs ``seq_len % fast == 0``; indivisible call sites fall back to the
    fused path, which is numerically identical).  Resolved from the knob,
    SP additionally requires a non-recurrent family (recurrences need the
    full sequence — the same gate ``build_train_step`` applies), and
    ``"auto"`` asks the active autotuner with this call site's residual
    message size — builders trace inside ``autotune.using(ar_table)``, so
    each executable dispatches against its own table (DESIGN.md §10).
    """
    if not ctx.tp_fast:
        return False
    fast = hier.axes_size(ctx.tp_fast)
    if fast <= 1 or seq_len % fast:
        return False
    if explicit is not None:
        return bool(explicit)
    mode = ctx.seq_parallel
    if mode == "off" or cfg.family in ("ssm", "hybrid"):
        return False
    if mode == "on":
        return True
    from ..core import autotune
    itemsize = jnp.dtype(cfg.dtype).itemsize
    slow = hier.axes_size(ctx.tp_slow) if ctx.tp_slow else 1
    return autotune.resolve_sp(n_tokens * cfg.d_model * itemsize, fast,
                               slow, jnp.dtype(cfg.dtype).name)


def _residual(x, partial, ctx: ParallelCtx, sp: bool):
    if sp:
        return x + hier.tp_reduce_scatter(partial, ctx, dim=1)
    return x + hier.tp_all_reduce(partial, ctx, scatter_dim=-1)


def _use_overlap(ctx: ParallelCtx) -> bool:
    """Route row-parallel output projections through the overlapped
    collective-matmul (the tentpole decode optimization)."""
    return ctx.overlap_matmul and ctx.has_tp


def _residual_proj(x, lhs, w, spec: str, ctx: ParallelCtx, sp: bool,
                   ef=None):
    """Residual add of projection + TP reduction, overlapped when enabled.

    ``lhs`` is the pre-projection activation, ``w`` the row-sharded weight
    with output features last; numerically identical to
    ``_residual(x, einsum(spec, lhs, w), ctx, sp)``.

    ``ef``: error-feedback residual for the quantized all-reduce, shaped
    like the projection output.  When given the return value is
    ``(x, new_ef)`` — same contract as ``tp_all_reduce`` (fp paths hand
    ``ef`` back untouched).  Decode-only: the SP branch never sees it."""
    if _use_overlap(ctx):
        if sp:
            return x + ov.collective_matmul_reduce_scatter(
                lhs, w, ctx, dim=1, spec=spec)
        if ef is not None:
            y, ef2 = ov.collective_matmul(lhs, w, ctx, spec=spec, ef=ef)
            return x + y, ef2
        return x + ov.collective_matmul(lhs, w, ctx, spec=spec)
    if ef is not None:
        y, ef2 = hier.tp_all_reduce(jnp.einsum(spec, lhs, w), ctx,
                                    scatter_dim=-1, ef=ef)
        return x + y, ef2
    return _residual(x, jnp.einsum(spec, lhs, w), ctx, sp)


def _gathered(x, ctx: ParallelCtx, sp: bool):
    return hier.tp_all_gather(x, ctx, dim=1) if sp else x


def _norm(x, p: Params, cfg):
    with jax.named_scope("norm"):
        return L.apply_norm(x, p, cfg)


def _head(params: Params, x, cfg, ctx: ParallelCtx, sp: bool = False):
    """Final norm and the (vocab-sharded) logits."""
    with jax.named_scope("head"):
        x = L.apply_norm(x, params["final_norm"], cfg)
        if sp:
            x = hier.tp_all_gather(x, ctx, dim=1)
        return L.lm_logits(params["embed"], x)


def _moe_tokens(h, ctx: ParallelCtx, sp: bool):
    """MoE consumes per-device-unique tokens: under SP the shard already is;
    otherwise slice this device's sequence chunk (no comm)."""
    if sp or not ctx.has_tp:
        return h
    tp = hier.axes_size(ctx.tp_axes)
    s_loc = h.shape[1] // tp
    start = L.tp_rank(ctx) * s_loc
    return lax.dynamic_slice_in_dim(h, start, s_loc, axis=1)


def _moe_restore(out, ctx: ParallelCtx, sp: bool):
    if sp or not ctx.has_tp:
        return out
    return lax.all_gather(out, ctx.tp_axes, axis=1, tiled=True)


def block_forward(bp: Params, x, ap: ArchPlan, ctx: ParallelCtx, *,
                  positions, sp: bool, causal: bool = True,
                  enc_kv=None, chunk: int = 0,
                  collect_state: bool = False):
    """One block, full sequence.  Returns (x, aux_loss, state_or_None)."""
    cfg = ap.cfg
    aux = jnp.zeros((), jnp.float32)
    state = {}
    if cfg.family == "ssm":
        h = _gathered(_norm(x, bp["ln1"], cfg), ctx, sp)
        if collect_state:
            tm, st = R.rwkv_time_mix(bp["tm"], h, cfg, ctx, return_state=True)
            state.update(st)
        else:
            tm = R.rwkv_time_mix(bp["tm"], h, cfg, ctx)
        x = _residual(x, tm, ctx, sp)
        h2 = _gathered(_norm(x, bp["ln2"], cfg), ctx, sp)
        if collect_state:
            stacked, st2 = R.rwkv_channel_mix(bp["cm"], h2, cfg, ctx,
                                              return_state=True)
            state.update(st2)
        else:
            stacked = R.rwkv_channel_mix(bp["cm"], h2, cfg, ctx)
        if sp:
            red = hier.tp_reduce_scatter(stacked, ctx, dim=2)
        else:
            red = hier.tp_all_reduce(stacked, ctx, scatter_dim=-1)
        x = x + jax.nn.sigmoid(red[1].astype(jnp.float32)).astype(x.dtype) \
            * red[0]
        return x, aux, (state or None)

    h = _gathered(_norm(x, bp["ln1"], cfg), ctx, sp)
    # hybrid mixes attn + ssm partials before reducing, so the projection
    # cannot be fused with the reduction there — overlap dense-ish only.
    attn_ov = _use_overlap(ctx) and cfg.family != "hybrid"
    attn_out, kv = _attention_with_kv(bp["attn"], h, ap, ctx,
                                      positions=positions, causal=causal,
                                      chunk=chunk, project=not attn_ov)
    if collect_state:
        state["k"], state["v"] = kv
    if cfg.family == "hybrid":
        if collect_state:
            ssm_out, st = S.ssm_mixer(bp["ssm"], h, cfg, ctx,
                                      return_state=True)
            state.update(st)
        else:
            ssm_out = S.ssm_mixer(bp["ssm"], h, cfg, ctx)
        beta = bp["beta"].astype(x.dtype)
        mix = beta[0] * attn_out + beta[1] * ssm_out
        x = _residual(x, mix, ctx, sp)
    elif attn_ov:
        x = _residual_proj(x, attn_out, bp["attn"]["wo"], "bsqh,qhd->bsd",
                           ctx, sp)
    else:
        x = _residual(x, attn_out, ctx, sp)

    if enc_kv is not None:
        hx = _gathered(_norm(x, bp["ln_x"], cfg), ctx, sp)
        xo = L.cross_attention(bp["xattn"], hx, enc_kv[0], enc_kv[1], cfg,
                               ap.gqa, ctx, ap.q_mask_tbl)
        x = _residual(x, xo, ctx, sp)

    h2 = _norm(x, bp["ln2"], cfg)
    if cfg.is_moe:
        toks = _moe_tokens(_gathered(h2, ctx, sp) if not sp else h2, ctx, sp)
        out, aux_l = M.moe_ffn(bp["moe"], toks, cfg, ctx, decode=False)
        if aux_l is not None:
            aux = aux + aux_l
        x = x + _moe_restore(out, ctx, sp)
    else:
        h2g = _gathered(h2, ctx, sp)
        with jax.named_scope("mlp"):
            if _use_overlap(ctx):
                x = _residual_proj(x, L.mlp_hidden(bp["mlp"], h2g, cfg),
                                   L.mlp_down_w(bp["mlp"], cfg),
                                   "bsf,fd->bsd", ctx, sp)
            else:
                x = _residual(x, L.mlp(bp["mlp"], h2g, cfg), ctx, sp)
    return x, aux, (state or None)


def _attention_with_kv(p, h, ap: ArchPlan, ctx, *, positions, causal, chunk,
                       project: bool = True):
    cfg = ap.cfg
    q, k, v = L._qkv_rope(p, h, cfg, ap.gqa, positions)
    with jax.named_scope("attention"):
        mask = L._mask(positions, positions, causal=causal,
                       window=cfg.sliding_window)
        o = L.attn_core(q, k, v, mask, ap.gqa.g, chunk=chunk)
        if ap.q_mask_tbl is not None:
            o = o * L.take_local(ap.q_mask_tbl, ctx)[None, None, :, None] \
                .astype(o.dtype)
    out = L._o_proj(o, p["wo"]) if project else o
    return out, (k, v)


# ---------------------------------------------------------------------------
# Encoder (whisper)
# ---------------------------------------------------------------------------


def encoder_forward(params: Params, frames, ap: ArchPlan, ctx: ParallelCtx,
                    *, sp: bool, scan_layers: bool = True, layer_map=None):
    """frames: (B, T_enc, D) precomputed frame embeddings (frontend stub)."""
    cfg = ap.cfg
    sp = sp and bool(ctx.tp_fast) and frames.shape[1] % max(ap.tp, 1) == 0
    x = _moe_tokens(frames, ctx, sp=False) if sp else frames
    positions = jnp.arange(frames.shape[1], dtype=jnp.int32)

    def body(x, bp):
        if layer_map is not None:
            bp = layer_map(bp)
        h = _gathered(L.apply_norm(x, bp["ln1"], cfg), ctx, sp)
        ao, _ = _attention_with_kv(bp["attn"], h, ap, ctx,
                                   positions=positions, causal=False,
                                   chunk=0)
        x = _residual(x, ao, ctx, sp)
        h2 = _gathered(L.apply_norm(x, bp["ln2"], cfg), ctx, sp)
        x = _residual(x, L.mlp(bp["mlp"], h2, cfg), ctx, sp)
        return x

    if scan_layers:
        x, _ = lax.scan(lambda c, bp: (body(c, bp), None),
                        x, params["enc_blocks"])
    else:
        nl = cfg.enc_layers
        for i in range(nl):
            bp = jax.tree.map(lambda t: t[i], params["enc_blocks"])
            x = body(x, bp)
    x = L.apply_norm(x, params["enc_norm"], cfg)
    return _gathered(x, ctx, sp)


# ---------------------------------------------------------------------------
# Full-sequence LM forward (train / prefill)
# ---------------------------------------------------------------------------


def forward_lm(params: Params, tokens, ap: ArchPlan, ctx: ParallelCtx, *,
               sp: Optional[bool] = None, scan_layers: bool = True,
               patch_embeds=None, frame_embeds=None,
               collect_state: bool = False, chunk: int = 0,
               layer_map=None, enc_layer_map=None, remat: bool = False):
    """Returns (logits_local, aux_loss, states_or_None, enc_out_or_None).

    logits_local: (B, S, V_local) vocab-sharded (a sequence-parallel
    residual stream is gathered back to full S before the vocab head).
    states: per-layer pytree stacked on a leading layer axis (prefill cache
    seeds) when ``collect_state``.

    ``sp=None`` (serve-side prefill builders) resolves the sequence-
    parallel layout from ``ctx.seq_parallel`` per call site; an explicit
    bool (the training step) forces it, subject to the divisibility guard
    (see :func:`_seq_parallel_active`).
    """
    cfg = ap.cfg
    B, Sq = tokens.shape
    sp_active = _seq_parallel_active(ctx, cfg, B * Sq, Sq, sp)
    if patch_embeds is None:
        x = L.embed_lookup(params["embed"], tokens, ctx, ap.vocab_pad,
                           sp=sp_active)
    else:
        x = L.embed_lookup(params["embed"], tokens, ctx, ap.vocab_pad,
                           sp=False)
        x = lax.dynamic_update_slice(
            x, patch_embeds.astype(x.dtype), (0, 0, 0))
        if sp_active:
            x = _moe_tokens(x, ctx, sp=False)  # free slice to seq-shards
    enc_out = None
    enc_kv_all = None
    if cfg.enc_layers:
        enc_out = encoder_forward(params, frame_embeds, ap, ctx,
                                  sp=sp_active, scan_layers=scan_layers,
                                  layer_map=enc_layer_map)
        # Precompute per-layer cross K/V once (also the decode cache seed).
        def xkv(bp):
            return L.cross_kv(bp["xattn"], enc_out)
        enc_kv_all = jax.vmap(xkv)(params["blocks"]) if scan_layers else None
    positions = jnp.arange(Sq, dtype=jnp.int32)
    sp = sp_active
    aux0 = jnp.zeros((), jnp.float32)

    def body(carry, layer_in):
        x, aux = carry
        bp, ekv = layer_in
        if layer_map is not None:
            bp = layer_map(bp)
        x, a, st = block_forward(bp, x, ap, ctx, positions=positions,
                                 sp=sp, causal=True, enc_kv=ekv,
                                 chunk=chunk, collect_state=collect_state)
        return (x, aux + a), st

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)

    if scan_layers:
        xs = (params["blocks"],
              enc_kv_all if cfg.enc_layers else None)
        (x, aux), states = lax.scan(body, (x, aux0), xs)
    else:
        states_list = []
        aux = aux0
        for i in range(cfg.n_layers):
            bp = jax.tree.map(lambda t: t[i], params["blocks"])
            ekv = L.cross_kv(bp["xattn"], enc_out) if cfg.enc_layers \
                else None
            (x, aux), st = body((x, aux), (bp, ekv))
            if st is not None:
                states_list.append(st)
        states = _stack(states_list) if states_list else None

    logits = _head(params, x, cfg, ctx, sp)
    return logits, aux, states, enc_out


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------


def ef_sites_for(ctx: ParallelCtx, cfg) -> int:
    """Error-feedback site count for ``init_cache(..., ef_sites=...)``.

    Dense decode threads EF through its two row-parallel reductions
    (attn wo, mlp down) whenever the ctx may quantize the wire
    (``ar_quant`` forced or "auto"); recurrent/hybrid families take the
    one-shot rounding and carry no EF leaf.  Every builder of one serving
    deployment must derive the count from the same (ctx, cfg) so cache
    pytrees stay structurally identical across steps."""
    if getattr(ctx, "ar_quant", "none") == "none" or cfg.family != "dense":
        return 0
    return 2


def init_cache(ap: ArchPlan, batch: int, s_max: int,
               local: bool = True, *, kv_quant: bool = False,
               window_cache: bool = False, block_size: int = 0,
               n_blocks: Optional[int] = None,
               ef_sites: int = 0) -> Params:
    """Decode cache pytree, leading layer axis.  ``local`` shapes are
    per-device (tp already divided out); global shapes otherwise.

    kv_quant: int8 K/V payloads + per-(pos, head) bf16 scales.
    window_cache: ring buffer of size sliding_window (SWA archs only).
    block_size > 0: *paged* K/V layout — physical blocks
    (L, n_blocks, block_size, u, hd) plus an int32 logical->physical
    ``block_tbl`` (batch, s_max // block_size).  ``block_size=0`` is the
    dense (batch, s_max) layout, the bit-parity degenerate case.  When
    ``n_blocks`` is None the pool holds every slot at full length plus the
    reserved trash block 0, and the table starts as the identity mapping
    (dense-equivalent without an allocator); a smaller explicit pool starts
    all-trash and must be managed by a
    :class:`repro.inference.kv_cache.BlockAllocator`.  Paging applies to
    the self-attention K/V only; recurrent / encoder leaves are tiny,
    fixed-size per-slot states and stay batch-indexed.
    ef_sites > 0: error-feedback residual for quantized all-reduce
    (``ctx.ar_quant``) — one f32 (d_model,) state per (layer, reduction
    site, device, slot), carried as the cache leaf ``ef`` with shape
    (L, ef_sites, tp, batch, d_model) so it rides the decode scan and
    slot admission for free.  The device dim is this rank's OWN rounding
    residual (sharded over TP); dense decode has two sites per layer
    (attn wo, mlp down).
    """
    cfg = ap.cfg
    tp = 1 if local else ap.tp
    c: Params = {}
    Ldec = cfg.n_layers
    if window_cache:
        assert cfg.sliding_window > 0, "window cache needs SWA"
        s_max = min(s_max, cfg.sliding_window)
    if not cfg.attn_free:
        u = ap.gqa.u * tp if not local else ap.gqa.u
        hd = cfg.head_dim
        if cfg.family != "ssm":
            kv_dt = jnp.int8 if kv_quant else cfg.dtype
            if block_size > 0:
                assert not kv_quant and not window_cache, \
                    "paged cache is incompatible with kv_quant/window_cache"
                assert s_max % block_size == 0, (s_max, block_size)
                max_blocks = s_max // block_size
                if n_blocks is None:
                    n_blocks = batch * max_blocks + 1
                c["k"] = jnp.zeros((Ldec, n_blocks, block_size, u, hd),
                                   kv_dt)
                c["v"] = jnp.zeros((Ldec, n_blocks, block_size, u, hd),
                                   kv_dt)
                if n_blocks >= batch * max_blocks + 1:
                    c["block_tbl"] = 1 + jnp.arange(
                        batch * max_blocks,
                        dtype=jnp.int32).reshape(batch, max_blocks)
                else:
                    c["block_tbl"] = jnp.zeros((batch, max_blocks),
                                               jnp.int32)
            else:
                c["k"] = jnp.zeros((Ldec, batch, s_max, u, hd), kv_dt)
                c["v"] = jnp.zeros((Ldec, batch, s_max, u, hd), kv_dt)
            if kv_quant:
                c["k_scale"] = jnp.zeros((Ldec, batch, s_max, u),
                                         jnp.bfloat16)
                c["v_scale"] = jnp.zeros((Ldec, batch, s_max, u),
                                         jnp.bfloat16)
    if cfg.family == "hybrid":
        ci = ap.d_inner_local if local else cfg.d_inner
        c["conv"] = jnp.zeros((Ldec, batch, cfg.d_conv - 1, ci), cfg.dtype)
        c["ssm"] = jnp.zeros((Ldec, batch, ci, cfg.ssm_state), jnp.float32)
    if cfg.family == "ssm":
        hloc = ap.rwkv_heads_local if local \
            else cfg.d_model // cfg.rwkv_head_dim
        c["shift_tm"] = jnp.zeros((Ldec, batch, cfg.d_model), cfg.dtype)
        c["shift_cm"] = jnp.zeros((Ldec, batch, cfg.d_model), cfg.dtype)
        c["wkv"] = jnp.zeros((Ldec, batch, hloc, cfg.rwkv_head_dim,
                              cfg.rwkv_head_dim), jnp.float32)
    if cfg.enc_layers:
        u = ap.gqa.u if local else ap.gqa.kv_slots
        c["enc_k"] = jnp.zeros((Ldec, batch, cfg.enc_seq, u, cfg.head_dim),
                               cfg.dtype)
        c["enc_v"] = jnp.zeros((Ldec, batch, cfg.enc_seq, u, cfg.head_dim),
                               cfg.dtype)
    if ef_sites > 0:
        c["ef"] = jnp.zeros((Ldec, ef_sites, tp, batch, cfg.d_model),
                            jnp.float32)
    return c


def _paged_splice(phys, states, block_tbl, slot):
    """Scatter prefill K/V states (L, B, S, U, hd) into the physical block
    pool (L, n_blocks, bs, U, hd) through the block table.  The trailing
    partial block is zero-padded; those positions are overwritten by decode
    writes before any unmasked read (same invariant as chunk padding)."""
    Ldec, B, S, u, hd = states.shape
    bs = phys.shape[2]
    nb = -(-S // bs)
    pad = nb * bs - S
    upd = states.astype(phys.dtype)
    if pad:
        upd = jnp.pad(upd, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    upd = upd.reshape(Ldec, B, nb, bs, u, hd)
    if slot is None:
        tgt = block_tbl[:, :nb]                       # (B, nb)
        return phys.at[:, tgt].set(upd)
    row = lax.dynamic_index_in_dim(block_tbl, slot, 0, keepdims=False)
    return phys.at[:, row[:nb]].set(upd[:, 0])


def seed_cache(cache: Params, states: Params, *, slot=None,
               enc_kv: Optional[Tuple[Any, Any]] = None) -> Params:
    """Splice prefill-collected layer states into a decode cache.

    The one shared cache-splice: the engine's local prefill, the mesh
    prefill builder and the continuous batcher's admission step all route
    through here (they used to carry three copies of this logic).

    ``slot=None``: batch-wide splice (states batch == cache batch), written
    at position 0.  ``slot`` (traced scalar ok): single-request splice
    (states batch == 1) into that cache row.  Paged caches
    (``cache['block_tbl']`` present) route K/V through the block table.
    ``enc_kv``: (enc_k, enc_v) per-layer cross-attention K/V for enc-dec.
    """
    out = dict(cache)
    if "k" in cache:
        if "block_tbl" in cache:
            out["k"] = _paged_splice(cache["k"], states["k"],
                                     cache["block_tbl"], slot)
            out["v"] = _paged_splice(cache["v"], states["v"],
                                     cache["block_tbl"], slot)
        elif "k_scale" in cache:
            # int8 KV target: a raw astype would truncate the fp states and
            # leave the scale rows zero (dequant -> 0), so the splice
            # quantizes with the same per-(pos, head) scales the decode
            # write path uses (layers.attention_decode).
            idx0 = (0, 0, 0, 0, 0) if slot is None else (0, slot, 0, 0, 0)

            def _q8(t):  # (L,B,S,U,hd) -> int8 payload + bf16 (L,B,S,U)
                tf = t.astype(jnp.float32)
                sc = jnp.maximum(jnp.max(jnp.abs(tf), axis=-1) / 127.0,
                                 1e-30)
                qq = jnp.clip(jnp.round(tf / sc[..., None]), -127, 127)
                return qq.astype(jnp.int8), sc.astype(jnp.bfloat16)

            kq, ksc = _q8(states["k"])
            vq, vsc = _q8(states["v"])
            out["k"] = lax.dynamic_update_slice(cache["k"], kq, idx0)
            out["v"] = lax.dynamic_update_slice(cache["v"], vq, idx0)
            out["k_scale"] = lax.dynamic_update_slice(cache["k_scale"],
                                                      ksc, idx0[:-1])
            out["v_scale"] = lax.dynamic_update_slice(cache["v_scale"],
                                                      vsc, idx0[:-1])
        else:
            idx0 = (0, 0, 0, 0, 0) if slot is None else (0, slot, 0, 0, 0)
            out["k"] = lax.dynamic_update_slice(
                cache["k"], states["k"].astype(cache["k"].dtype), idx0)
            out["v"] = lax.dynamic_update_slice(
                cache["v"], states["v"].astype(cache["v"].dtype), idx0)
    for nm in ("conv", "ssm", "shift_tm", "shift_cm", "wkv"):
        if nm in cache:
            upd = states[nm].astype(cache[nm].dtype)
            if slot is None:
                out[nm] = upd
            else:
                idx = (0, slot) + (0,) * (cache[nm].ndim - 2)
                out[nm] = lax.dynamic_update_slice(cache[nm], upd, idx)
    if "ef" in cache:
        # A fresh request starts with no accumulated rounding residual —
        # stale EF from the slot's previous occupant must never leak into
        # the new request's reductions.
        if slot is None:
            out["ef"] = jnp.zeros_like(cache["ef"])
        else:
            zero = jnp.zeros(cache["ef"].shape[:3] + (1,)
                             + cache["ef"].shape[4:], cache["ef"].dtype)
            out["ef"] = lax.dynamic_update_slice(cache["ef"], zero,
                                                 (0, 0, 0, slot, 0))
    if enc_kv is not None and "enc_k" in cache:
        ek, ev = enc_kv
        if slot is None:
            out["enc_k"] = ek.astype(cache["enc_k"].dtype)
            out["enc_v"] = ev.astype(cache["enc_v"].dtype)
        else:
            idx = (0, slot, 0, 0, 0)
            out["enc_k"] = lax.dynamic_update_slice(
                cache["enc_k"], ek.astype(cache["enc_k"].dtype), idx)
            out["enc_v"] = lax.dynamic_update_slice(
                cache["enc_v"], ev.astype(cache["enc_v"].dtype), idx)
    return out


def block_decode(bp: Params, x, cache_l: Params, ap: ArchPlan,
                 ctx: ParallelCtx, *, positions,
                 attn_chunk=None, kv_ring: bool = False,
                 block_tbl=None, kv_lens=None) -> Tuple[Any, Params]:
    """One block, one token.  x: (B,1,D) replicated; cache_l: this layer's
    cache slice; ``kv_lens`` the positions each slot attends in a paged
    cache (see ``layers.attention_decode``).  Returns (x, new_cache_l).
    Every sublayer output is a TP-partial reduced by tp_all_reduce — the
    collective the paper targets.

    When the cache carries an ``ef`` leaf (quantized all-reduce with error
    feedback, shape (sites, 1, B, D) per layer locally), the dense attn-wo
    and mlp-down reductions consume and refresh their per-site residual;
    every other reduction site takes the one-shot rounding.
    """
    cfg = ap.cfg
    new_c: Params = {}
    ef = cache_l.get("ef") if isinstance(cache_l, dict) else None

    def _ef_in(site):
        # (sites, 1, B, D) -> (B, 1, D): the message layout of one token
        return jnp.swapaxes(ef[site], 0, 1)

    if cfg.family == "ssm":
        h = _norm(x, bp["ln1"], cfg)
        tm, st = R.rwkv_time_mix_step(
            bp["tm"], h, {"shift_tm": cache_l["shift_tm"],
                          "wkv": cache_l["wkv"]}, cfg, ctx)
        new_c["shift_tm"], new_c["wkv"] = st["shift_tm"], st["wkv"]
        x = x + hier.tp_all_reduce(tm, ctx, scatter_dim=-1)
        h2 = _norm(x, bp["ln2"], cfg)
        stacked, st2 = R.rwkv_channel_mix(
            bp["cm"], h2, cfg, ctx, state={"shift_cm": cache_l["shift_cm"]},
            return_state=True)
        new_c["shift_cm"] = st2["shift_cm"]
        red = hier.tp_all_reduce(stacked, ctx, scatter_dim=-1)
        x = x + jax.nn.sigmoid(red[1].astype(jnp.float32)).astype(x.dtype) \
            * red[0]
        if ef is not None:
            new_c["ef"] = ef
        return x, new_c

    h = _norm(x, bp["ln1"], cfg)
    kv_in = {k2: cache_l[k2] for k2 in
             ("k", "v", "k_scale", "v_scale") if k2 in cache_l}
    # Decode is the paper's regime: the wo projection + all-reduce pair
    # routes through _residual_proj (overlapped when ctx asks for it).
    # hybrid mixes attn+ssm partials pre-reduce, so it cannot fuse and
    # keeps the projected-partial form.
    hybrid = cfg.family == "hybrid"
    attn_out, kv_new = L.attention_decode(
        bp["attn"], h, kv_in, cfg, ap.gqa,
        ctx, positions=positions, q_mask_tbl=ap.q_mask_tbl,
        chunk=attn_chunk, ring=kv_ring, project=hybrid,
        block_tbl=block_tbl, kv_lens=kv_lens)
    new_c.update(kv_new)
    ef_attn = ef_mlp = None
    if hybrid:
        so, st = S.ssm_step(bp["ssm"], h, {"conv": cache_l["conv"],
                                           "ssm": cache_l["ssm"]}, cfg, ctx)
        new_c["conv"], new_c["ssm"] = st["conv"], st["ssm"]
        beta = bp["beta"].astype(x.dtype)
        x = x + hier.tp_all_reduce(beta[0] * attn_out + beta[1] * so, ctx,
                                   scatter_dim=-1)
        if ef is not None:
            ef_attn = _ef_in(0)
    elif ef is not None:
        with jax.named_scope("o_proj"):
            x, ef_attn = _residual_proj(x, attn_out, bp["attn"]["wo"],
                                        "bsqh,qhd->bsd", ctx, sp=False,
                                        ef=_ef_in(0))
    else:
        with jax.named_scope("o_proj"):
            x = _residual_proj(x, attn_out, bp["attn"]["wo"],
                               "bsqh,qhd->bsd", ctx, sp=False)

    if cfg.enc_layers:
        hx = _norm(x, bp["ln_x"], cfg)
        xo = L.cross_attention(bp["xattn"], hx, cache_l["enc_k"],
                               cache_l["enc_v"], cfg, ap.gqa, ctx,
                               ap.q_mask_tbl)
        x = x + hier.tp_all_reduce(xo, ctx, scatter_dim=-1)
        new_c["enc_k"], new_c["enc_v"] = cache_l["enc_k"], cache_l["enc_v"]

    h2 = _norm(x, bp["ln2"], cfg)
    if cfg.is_moe:
        with jax.named_scope("mlp"):
            out = M.moe_ffn_dense(bp["moe"], h2, cfg, ctx)
            x = x + hier.tp_all_reduce(out, ctx, scatter_dim=-1)
        if ef is not None:
            ef_mlp = _ef_in(1)
    elif ef is not None:
        with jax.named_scope("mlp"):
            x, ef_mlp = _residual_proj(x, L.mlp_hidden(bp["mlp"], h2, cfg),
                                       L.mlp_down_w(bp["mlp"], cfg),
                                       "bsf,fd->bsd", ctx, sp=False,
                                       ef=_ef_in(1))
    else:
        with jax.named_scope("mlp"):
            x = _residual_proj(x, L.mlp_hidden(bp["mlp"], h2, cfg),
                               L.mlp_down_w(bp["mlp"], cfg), "bsf,fd->bsd",
                               ctx, sp=False)
    if ef is not None:
        new_c["ef"] = jnp.stack([jnp.swapaxes(ef_attn, 0, 1),
                                 jnp.swapaxes(ef_mlp, 0, 1)])
    return x, new_c


def _layers_in_place(body, x, blocks, cache, block_tbl=None, *,
                     scan: bool = True):
    """Run ``body(x, layer_params, layer_cache, layer_tbl, trash) -> (x,
    new_layer_cache)`` over the layers, the stacked cache updated where it
    lies.

    Scanned, the stacked cache is carried through the loop and each
    layer's slice is written back where it was read (scope ``kv_carry``).
    Scanning the cache as ``xs`` and collecting it as ``ys`` would give XLA
    a second stacked cache to fill (a full copy of the KV pool alive at
    once); carried, the donated cache is updated in place, one layer's
    slice at a time.  Unscanned (``scan=False``), each layer's slice is
    taken and the slices are stacked again.

    A paged cache (``block_tbl`` given) is not sliced at all: its K/V pools
    (L, n_blocks, bs, U, hd) are viewed once as one pool (L*n_blocks, bs,
    U, hd) -- a bitcast, the merged dims are never sharded -- and layer
    ``i`` addresses its own blocks through ``block_tbl + i*n_blocks``, its
    reserved trash block being ``i*n_blocks``.  The body's scatter and
    gather then work on the whole pool, so no layer's pool is copied out
    and back; ``kv_carry`` holds only that index offset and the two views.
    ``layer_tbl`` is None and ``trash`` 0 for every other layout.  Every
    other leaf (dense K/V, int8 scales, recurrent states, encoder K/V,
    ``ef``) is sliced and written back.  Same per-layer math, same
    values."""
    n_layers = jax.tree.leaves(blocks)[0].shape[0]
    pools = {}
    if block_tbl is not None:
        pool_shape = cache["k"].shape
        with jax.named_scope("kv_carry"):
            pools = {k2: cache[k2].reshape((-1,) + pool_shape[2:])
                     for k2 in ("k", "v")}
        cache = {k2: t for k2, t in cache.items() if k2 not in pools}

    def layer_tbl(i):
        if block_tbl is None:
            return None, 0
        with jax.named_scope("kv_carry"):
            trash = i * pool_shape[1]
            return block_tbl + trash, trash

    def with_pools(cl, pools):
        return {**cl, **pools} if pools else cl

    if scan:
        def step(carry, inp):
            x, pools, cache = carry
            i, bp = inp
            with jax.named_scope("kv_carry"):
                cl = jax.tree.map(
                    lambda t: lax.dynamic_index_in_dim(t, i, keepdims=False),
                    cache)
            x, nc = body(x, bp, with_pools(cl, pools), *layer_tbl(i))
            pools = {k2: nc.pop(k2) for k2 in pools}
            with jax.named_scope("kv_carry"):
                cache = jax.tree.map(
                    lambda t, u: lax.dynamic_update_index_in_dim(
                        t, u.astype(t.dtype), i, 0), cache, nc)
            return (x, pools, cache), None

        (x, pools, cache), _ = lax.scan(
            step, (x, pools, cache),
            (jnp.arange(n_layers, dtype=jnp.int32), blocks))
    else:
        ncs = []
        for i in range(n_layers):
            bp = jax.tree.map(lambda t: t[i], blocks)
            cl = jax.tree.map(lambda t: t[i], cache)
            x, nc = body(x, bp, with_pools(cl, pools), *layer_tbl(i))
            pools = {k2: nc.pop(k2) for k2 in pools}
            ncs.append(nc)
        cache = _stack(ncs)
    if pools:
        with jax.named_scope("kv_carry"):
            cache = {**cache, **{k2: p.reshape(pool_shape)
                                 for k2, p in pools.items()}}
    return x, cache


def decode_step(params: Params, cache: Params, tokens, positions,
                ap: ArchPlan, ctx: ParallelCtx, *,
                scan_layers: bool = True, layer_map=None,
                attn_chunk=None, kv_ring: bool = False, kv_lens=None):
    """One decode step for the whole batch.

    tokens: (B,) int32; positions: (B,) write index.  Returns
    (logits_local (B, V_loc), new_cache).

    A paged cache (``cache['block_tbl']`` present) routes K/V writes/reads
    through the table; the table itself has no layer axis, so it rides
    outside the layer scan and is returned unchanged.  ``kv_lens`` (B,)
    bounds the positions each slot reads there (default ``positions + 1``;
    0 for a slot whose output is discarded).
    """
    cfg = ap.cfg
    block_tbl = cache.get("block_tbl") if isinstance(cache, dict) else None
    if block_tbl is not None:
        cache = {k2: v for k2, v in cache.items() if k2 != "block_tbl"}
    x = L.embed_lookup(params["embed"], tokens[:, None], ctx, ap.vocab_pad)

    def body(x, bp, cl, tbl, trash):
        if layer_map is not None:
            bp = layer_map(bp)
        return block_decode(bp, x, cl, ap, ctx, positions=positions,
                            attn_chunk=attn_chunk, kv_ring=kv_ring,
                            block_tbl=tbl, kv_lens=kv_lens)

    x, new_cache = _layers_in_place(body, x, params["blocks"], cache,
                                    block_tbl, scan=scan_layers)
    if block_tbl is not None:
        new_cache["block_tbl"] = block_tbl
    logits = _head(params, x, cfg, ctx)[:, 0]
    return logits, new_cache


def prefill_chunk(params: Params, cache: Params, tokens, positions,
                  ap: ArchPlan, ctx: ParallelCtx, *,
                  scan_layers: bool = True, layer_map=None,
                  attn_chunk: int = 0, slot=None,
                  return_logits: bool = True,
                  sp: Optional[bool] = None):
    """Chunked prefill: run C prompt tokens against the decode cache.

    tokens: (B, C) int32; positions: (B, C) write positions.  Returns
    (logits_local (B, C, V_loc), new_cache).  With ``slot`` (traced scalar),
    B must be 1 and the chunk is spliced into that row of a batch-wide
    cache — the continuous batcher's jitted admission step, replacing the
    host-side ``dynamic_update_slice`` round trips.
    ``return_logits=False`` skips the final norm + vocab head entirely
    (logits come back None) — intermediate chunks only feed the cache.

    ``sp`` selects the sequence-parallel residual layout (default: resolve
    from ``ctx.seq_parallel`` on this chunk's message size, like
    ``forward_lm``): the residual stream stays sharded on the chunk dim
    over the fast TP axes, the post-``wo``/post-``wd`` projections end in
    ``tp_reduce_scatter``, norms run on sequence shards, and
    ``tp_all_gather`` restores the full chunk only for the QKV / up-proj
    inputs — bitwise-equal to the fused path, with per-collective wire
    bytes halved and activations between collectives shrunk by the
    fast-axis size (DESIGN.md §10).  K/V writes always see the full
    chunk, so the cache contents are layout-independent.

    Attention-only families (dense) only: recurrent states (ssm/hybrid/
    rwkv) advance token-by-token and cannot skip pad tokens, and MoE
    routing capacity is load-dependent, so those families admit via the
    full-prefill path instead (see ``parallel.steps.build_admit_step``).
    """
    cfg = ap.cfg
    if cfg.family != "dense":
        raise NotImplementedError(
            f"chunked prefill supports attention-only dense families, "
            f"not {cfg.family!r}")
    if "k_scale" in cache:
        raise NotImplementedError("chunked prefill with kv_quant")
    B, C = tokens.shape
    sp = _seq_parallel_active(ctx, cfg, B * C, C, sp)
    block_tbl = cache.get("block_tbl")
    # The EF residual is a decode-loop state keyed to the (B, 1, D) token
    # message; prefill reductions over (B, C, D) chunks take the one-shot
    # rounding and the admitted slot's decode EF restarts from zero.
    ef_buf = cache.get("ef")
    kv_cache = {k2: v for k2, v in cache.items()
                if k2 not in ("block_tbl", "ef")}
    x = L.embed_lookup(params["embed"], tokens, ctx, ap.vocab_pad, sp=sp)

    def body(x, bp, cl, tbl, trash):
        if layer_map is not None:
            bp = layer_map(bp)
        h = _gathered(_norm(x, bp["ln1"], cfg), ctx, sp)
        # Same residual idiom as block_decode: unprojected attention output
        # through _residual_proj (overlapped when ctx asks for it).
        attn_out, kv_new = L.attention_chunk_step(
            bp["attn"], h, cl, cfg, ap.gqa, ctx, positions=positions,
            q_mask_tbl=ap.q_mask_tbl, chunk=attn_chunk,
            project=False, block_tbl=tbl, slot=slot, trash=trash)
        with jax.named_scope("o_proj"):
            x = _residual_proj(x, attn_out, bp["attn"]["wo"],
                               "bsqh,qhd->bsd", ctx, sp=sp)
        h2 = _gathered(_norm(x, bp["ln2"], cfg), ctx, sp)
        with jax.named_scope("mlp"):
            x = _residual_proj(x, L.mlp_hidden(bp["mlp"], h2, cfg),
                               L.mlp_down_w(bp["mlp"], cfg), "bsf,fd->bsd",
                               ctx, sp=sp)
        return x, kv_new

    x, new_cache = _layers_in_place(body, x, params["blocks"], kv_cache,
                                    block_tbl, scan=scan_layers)
    if block_tbl is not None:
        new_cache["block_tbl"] = block_tbl
    if ef_buf is not None:
        if slot is None:
            new_cache["ef"] = jnp.zeros_like(ef_buf)
        else:
            zero = jnp.zeros(ef_buf.shape[:3] + (1,) + ef_buf.shape[4:],
                             ef_buf.dtype)
            new_cache["ef"] = lax.dynamic_update_slice(
                ef_buf, zero, (0, 0, 0, slot, 0))
    if not return_logits:
        return None, new_cache
    return _head(params, x, cfg, ctx, sp), new_cache


__all__ = ["ArchPlan", "make_plan", "init_params", "init_cache",
           "ef_sites_for", "forward_lm", "decode_step", "prefill_chunk",
           "seed_cache", "block_forward", "block_decode",
           "encoder_forward"]
