"""Continuous batching over the decode step (Orca-style), plus trace replay.

The scheduler owns a fixed pool of batch slots.  Each engine step decodes all
active slots; freed slots (finished requests) are refilled from the waiting
queue.  Positions are per-slot, so the single decode-step executable serves
ragged batches — the mechanism the paper's trace evaluation (Sec. 5.2.3)
relies on.

This is the *unified serving stack* over the paged KV-cache subsystem:

* the per-step work (decode + sampling + token/position/remaining update)
  is one jitted executable built by ``parallel.steps.build_serve_step`` —
  with ``mesh=None`` it runs single-device, with a mesh it is the
  shard_map'd production step inheriting ``ar_table`` (``ar_strategy=
  "auto"``) and ``ctx.overlap_matmul``.  The host only reads back the
  emitted tokens and done flags.
* admission is a jitted on-device splice (``build_admit_step`` /
  ``build_admit_chunk_step``), not host ``dynamic_update_slice`` round
  trips.  ``admit_mode="chunked"`` feeds prompts through a fixed-size
  chunked prefill (one executable for all lengths; dense families);
  ``"full"`` runs one prefill executable per distinct prompt length
  (every family).
* with ``block_size > 0`` the KV cache is paged: a host-side
  :class:`~repro.inference.kv_cache.BlockAllocator` grows each slot's
  block list on demand and *preempts* (evicts + requeues) the youngest
  request when the pool runs dry, so a slot count that would overflow a
  dense ``(slots, s_max)`` cache keeps serving.
* with ``spec_mode`` set, the per-step executable becomes the fused
  draft-verify step (``build_spec_verify_step``): slots advance by
  variable accepted lengths, rejected-draft K/V rolls back via
  ``BlockAllocator.truncate``, and the per-layer all-reduce is amortized
  over up to ``spec_k + 1`` tokens per step (DESIGN.md §8).

* for disaggregated serving, :meth:`ContinuousBatcher.admit_prefilled`
  admits a context prefilled by *another pool*: a canonical
  :class:`~repro.inference.kv_cache.KVBundle` is resharded into this
  batcher's GQA slot layout and spliced on device
  (``build_kv_splice_step``); ``inference.disagg.DisaggCoordinator``
  drives the batcher step-by-step in that deployment (DESIGN.md §9).

Scheduling time is a logical step clock (1.0 per engine step) so traces
replay deterministically; each request also carries wall-clock stamps
(``time.perf_counter()`` seconds) of its admission, first token and
release.

Host time is named for a profiler trace by ``TraceAnnotation`` spans:
``serve.tick``; ``serve.admit`` (keyword ``rid``, ``chunks``) with
``.table``, ``.chunks`` and ``.first_token`` inside; ``serve.step`` with
``.prepare``, ``.dispatch``, ``.readback`` and ``.commit`` inside (the
speculative step is one ``serve.step``).  With the profiler off a span
costs about a microsecond.  :meth:`ContinuousBatcher.op_scopes` names the
device ops of the same trace by the model's ``jax.named_scope``.

Invariants inherited from the cache layer (see ``kv_cache``): block-0 is
trash (stale-slot writes are routed there, never read), and freed blocks
may hold stale K/V (write-ordering: re-read only after overwrite).  Known
gaps: chunked admission and speculative decoding are dense-family-only
(recurrent states cannot skip pads; MoE routing is load-dependent), and a
paged mesh cache cannot shard slots over dp axes — run one batcher per
data-parallel replica.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.pcontext import ParallelCtx, LOCAL
from ..launch.hlo_analysis import module_name, op_scopes
from ..parallel.steps import (build_admit_chunk_step, build_admit_step,
                              build_cache_init, build_kv_splice_step,
                              build_serve_step, build_spec_verify_step,
                              local_sharding, serve_param_shardings)
from .faults import FaultInjector
from .kv_cache import (BlockAllocator, KVBundle, heads_to_slots,
                       paged_geometry)
from .prefix_cache import PrefixCache
from .speculative import AdaptiveK, Drafter, make_drafter


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (S,)
    max_new: int
    arrival_s: float = 0.0       # logical (step-clock) arrival
    # TTFT deadline in logical steps from arrival (inf = none); expired
    # never-admitted requests are shed, not served (DESIGN.md §11)
    deadline_s: float = float("inf")
    # filled by the scheduler, wall clock (time.perf_counter() seconds):
    admitted_s: float = -1.0     # the (last) admission began
    first_token_s: float = -1.0  # the (last) admission handed out a token
    done_s: float = -1.0         # released
    admit_step: int = -1         # logical step of (last) admission
    done_step: int = -1
    preempted: int = 0           # times evicted and recomputed
    output: Optional[np.ndarray] = None
    # shed bookkeeping: a shed request's output stays None, but it is
    # always *reported* (shed_reason set, counted in metrics) — the
    # never-silently-dropped contract
    shed_step: int = -1
    shed_reason: Optional[str] = None


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) \
        else float("nan")


def request_sampling_key(seed: int, rid: int) -> jax.Array:
    """Base of request ``rid``'s per-slot sampling chain.

    Token ``t`` of the request is drawn with ``fold_in(base, t)`` — a
    *stateless* chain keyed on the request, not on the global step
    schedule.  Consequences the suite relies on: sampled streams are
    identical across colocated and disaggregated deployments (the base key
    travels in ``KVBundle.rng``), and a preempted request's recompute
    resamples its original tokens.  temperature=0 paths never consult it.
    """
    return jax.random.fold_in(jax.random.PRNGKey(seed), rid)


def local_out(mesh, device) -> Dict[str, Any]:
    """``jax.jit`` keywords that put a mesh-less step's output (a fresh
    cache) on ``device``; a mesh step places its outputs itself."""
    home = local_sharding(mesh, device)
    return {} if home is None else {"out_shardings": home}


def run_chunked_prefill(params, cache, prompt: np.ndarray, slot, chunk: int,
                        mid_fn, final_fn, mid_rng, final_rng,
                        start_chunk: int = 0):
    """Drive a prompt through the chunked-prefill executables into cache
    row ``slot``: intermediate chunks via the logits-free ``mid_fn``
    (``mid_rng`` untouched — nothing samples), the final chunk via
    ``final_fn`` which samples the first token at in-chunk index
    ``(S-1) % chunk``.  Shared by colocated admission
    (:meth:`ContinuousBatcher._admit`) and the disaggregated prefill pool
    — the bitwise-parity guarantee between those deployments depends on
    this being ONE code path.  Returns (first_token_dev, cache).

    ``start_chunk`` skips the leading chunks whose K/V the slot's table
    already maps (a prefix-cache hit, DESIGN.md §14): chunk ``i`` writes
    the same values at the same positions regardless of where the loop
    starts, so a suffix prefill over spliced shared blocks is
    bit-identical to the full one.  ``start_chunk`` must be < n_chunks —
    the final chunk always runs (it samples the first token)."""
    S = int(prompt.shape[0])
    padded = np.zeros((-(-S // chunk) * chunk,), np.int32)
    padded[:S] = prompt
    n_chunks = padded.shape[0] // chunk
    assert 0 <= start_chunk < n_chunks, (start_chunk, n_chunks)
    last = jnp.int32((S - 1) % chunk)
    slot = jnp.int32(slot)
    tok = None
    for i in range(start_chunk, n_chunks):
        x = jnp.asarray(padded[None, i * chunk:(i + 1) * chunk])
        pos = jnp.arange(i * chunk, (i + 1) * chunk,
                         dtype=jnp.int32)[None]
        if i < n_chunks - 1:
            cache = mid_fn(params, cache, x, pos, slot, last, mid_rng)
        else:
            tok, cache = final_fn(params, cache, x, pos, slot, last,
                                  final_rng)
    return tok, cache


@dataclasses.dataclass
class ServeMetrics:
    """Aggregate trace-replay metrics.

    TTFT (time-to-first-token) and TPOT (time-per-output-token) are
    reported in logical *steps* (deterministic: admission wait + prefill
    counts 1 step), so the numbers are stable under CI jitter.  Wall
    times belong to a clock outside the scheduler: the stamps on each
    :class:`Request`.

    Speculative-decoding fields (all zero when ``spec_mode`` is off):

    * ``spec_steps``    — verify passes run (each replaces up to k+1
      sequential decode steps).
    * ``drafted_tokens`` / ``accepted_tokens`` — totals over the trace;
      ``acceptance_rate`` is their ratio (fraction of drafted tokens the
      target model verified — counted at verification, so a request
      terminating mid-run can verify more drafts than it emits).
    * ``accepted_tokens_per_step`` — mean verified drafts per verify
      pass across all slots: the per-step all-reduce amortization factor
      (an upper bound on emitted-tokens-per-step minus the active-slot
      count, tight when no request terminates mid-speculation).
    * ``drafter_hit_rate`` — fraction of ``draft()`` calls where the
      drafter found a real candidate instead of falling back.
    * ``spec_k_mean`` — mean speculation length used (moves under
      ``spec_adaptive``).
    """
    requests: int
    completed: int
    total_new_tokens: int
    steps: int
    wall_s: float
    throughput_tok_s: float
    ttft_steps_p50: float
    ttft_steps_p99: float
    tpot_steps_p50: float
    tpot_steps_p99: float
    preemptions: int
    peak_kv_tokens: int          # high-water cache footprint, in tokens
    kv_capacity_tokens: int      # reserved footprint of the layout
    cache_utilization: float     # occupied / reserved at peak-usage basis
    # paged decode steps: mean over steps of the K/V blocks the step's
    # attention reads (each live slot's ``ceil((pos + 1) / block_size)``)
    # over the whole table (``slots * max_blocks``); 0 when not paged
    kv_read_share: float = 0.0
    cache_stats: Optional[Dict[str, Any]] = None
    # speculative decoding (see class docstring; zeros when disabled)
    spec_steps: int = 0
    drafted_tokens: int = 0
    accepted_tokens: int = 0
    acceptance_rate: float = 0.0
    accepted_tokens_per_step: float = 0.0
    drafter_hit_rate: float = 0.0
    spec_k_mean: float = 0.0
    # robustness (DESIGN.md §11; zeros on a fault-free run):
    # * ``quarantines``       — slots evicted on non-finite logits and
    #   recomputed through the preemption path (exact replay).
    # * ``injected_oom``      — growths denied by an injected OOM burst
    #   (the growing slot is evicted + requeued, not its neighbours).
    # * ``shed_requests``     — deadline-expired requests dropped *before*
    #   admission; reported (``shed_reason``), never silently lost.
    # * ``spec_autodisables`` — slots whose speculative decoding was
    #   degraded to plain decode (verify fault or acceptance collapse).
    # * ``straggler_steps``   — steps carrying an injected wall-clock
    #   delay (logical clock untouched: latency noise, not token change).
    # * ``wasted_tokens``     — tokens decoded then discarded by an
    #   eviction (preemption / OOM / quarantine) and re-decoded from
    #   scratch; ``total / (total + wasted)`` is the useful-work goodput
    #   fraction ``benchmarks/bench_faults.py`` gates on.
    quarantines: int = 0
    injected_oom: int = 0
    shed_requests: int = 0
    spec_autodisables: int = 0
    straggler_steps: int = 0
    wasted_tokens: int = 0
    # prefix cache (DESIGN.md §14; zeros when ``prefix_cache="off"``):
    # * ``prefix_lookups``      — admissions that consulted the trie.
    # * ``prefix_hits``         — admissions that spliced >= 1 chunk.
    # * ``prefix_tokens_saved`` — prompt tokens never re-prefilled (each
    #   skipped an entire chunk of per-layer TP all-reduces).
    # * ``prefix_hit_rate``     — hits / lookups.
    prefix_lookups: int = 0
    prefix_hits: int = 0
    prefix_tokens_saved: int = 0
    prefix_hit_rate: float = 0.0
    # Retained per-request latency samples (logical steps) so a fleet
    # aggregation can recompute exact percentiles instead of averaging
    # per-replica p99s (see :meth:`merge`).  Excluded from ``to_dict`` —
    # bench JSON rows stay scalar-only.
    ttft_steps_samples: List[float] = dataclasses.field(
        default_factory=list, repr=False)
    tpot_steps_samples: List[float] = dataclasses.field(
        default_factory=list, repr=False)

    SAMPLE_FIELDS = ("ttft_steps_samples", "tpot_steps_samples")

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        for k in self.SAMPLE_FIELDS:
            d.pop(k, None)
        return d

    @classmethod
    def merge(cls, parts: List["ServeMetrics"]) -> "ServeMetrics":
        """Lossless fleet aggregation over per-replica metrics.

        Counters and token totals are summed; TTFT/TPOT percentiles are
        **recomputed from the retained samples** (a mean/max of
        per-replica p99s is not a fleet p99 — the average-of-averages
        bug this method exists to avoid); ``steps``/``wall_s`` take the
        max because replicas advance in lockstep on one shared logical
        clock; footprints sum (a fleet reserves every replica's cache);
        ratio fields are recomputed from the summed numerators and
        denominators.  ``cache_stats`` is dropped (per-replica detail —
        the router keeps the unmerged parts alongside)."""
        if not parts:
            raise ValueError("merge() needs at least one ServeMetrics")
        ttft = [s for m in parts for s in m.ttft_steps_samples]
        tpot = [s for m in parts for s in m.tpot_steps_samples]
        steps = max(m.steps for m in parts)
        wall = max(m.wall_s for m in parts)
        total_new = sum(m.total_new_tokens for m in parts)
        drafted = sum(m.drafted_tokens for m in parts)
        accepted = sum(m.accepted_tokens for m in parts)
        spec_steps = sum(m.spec_steps for m in parts)
        peak = sum(m.peak_kv_tokens for m in parts)
        # sum of per-replica occupied peaks over sum of reserved peaks
        occupied = sum(m.cache_utilization * m.peak_kv_tokens
                       for m in parts)
        wsum = lambda f: sum(getattr(m, f) * m.spec_steps for m in parts)
        return cls(
            requests=sum(m.requests for m in parts),
            completed=sum(m.completed for m in parts),
            total_new_tokens=total_new, steps=steps, wall_s=wall,
            throughput_tok_s=total_new / wall if wall > 0 else 0.0,
            ttft_steps_p50=_percentile(ttft, 50),
            ttft_steps_p99=_percentile(ttft, 99),
            tpot_steps_p50=_percentile(tpot, 50),
            tpot_steps_p99=_percentile(tpot, 99),
            preemptions=sum(m.preemptions for m in parts),
            peak_kv_tokens=peak,
            kv_capacity_tokens=sum(m.kv_capacity_tokens for m in parts),
            cache_utilization=occupied / peak if peak else 0.0,
            kv_read_share=sum(m.kv_read_share * m.steps for m in parts)
            / sum(m.steps for m in parts)
            if sum(m.steps for m in parts) else 0.0,
            cache_stats=None,
            spec_steps=spec_steps,
            drafted_tokens=drafted, accepted_tokens=accepted,
            acceptance_rate=accepted / drafted if drafted else 0.0,
            accepted_tokens_per_step=accepted / spec_steps
            if spec_steps else 0.0,
            drafter_hit_rate=wsum("drafter_hit_rate") / spec_steps
            if spec_steps else 0.0,
            spec_k_mean=wsum("spec_k_mean") / spec_steps
            if spec_steps else 0.0,
            quarantines=sum(m.quarantines for m in parts),
            injected_oom=sum(m.injected_oom for m in parts),
            shed_requests=sum(m.shed_requests for m in parts),
            spec_autodisables=sum(m.spec_autodisables for m in parts),
            straggler_steps=sum(m.straggler_steps for m in parts),
            wasted_tokens=sum(m.wasted_tokens for m in parts),
            prefix_lookups=sum(m.prefix_lookups for m in parts),
            prefix_hits=sum(m.prefix_hits for m in parts),
            prefix_tokens_saved=sum(m.prefix_tokens_saved for m in parts),
            prefix_hit_rate=sum(m.prefix_hits for m in parts) /
            sum(m.prefix_lookups for m in parts)
            if sum(m.prefix_lookups for m in parts) else 0.0,
            ttft_steps_samples=ttft, tpot_steps_samples=tpot)


class ContinuousBatcher:
    """Slot-based continuous batching on the local or mesh engine path."""

    def __init__(self, ap, params, *, slots: int = 8, s_max: int = 512,
                 ctx: ParallelCtx = LOCAL, mesh=None,
                 block_size: int = 0, n_blocks: Optional[int] = None,
                 kv_quant: bool = False,
                 ar_table: Optional[str] = None,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 scan_layers: bool = True, fsdp_serve: bool = False,
                 admit_mode: str = "full", admit_chunk: int = 32,
                 spec_mode: Optional[str] = None, spec_k: int = 4,
                 spec_adaptive: bool = False,
                 draft_arch: str = "llama3.2-1b",
                 drafter: Optional[Drafter] = None,
                 injector: Optional[FaultInjector] = None,
                 deadline_s: Optional[float] = None,
                 spec_autodisable_after: int = 0,
                 prefix_cache: str = "off",
                 prefix_capacity: Optional[int] = None,
                 device=None):
        """``device`` is where a mesh-less (``mesh=None``) batcher keeps
        its weights and cache (``jax.devices()[0]`` when None); with a mesh
        both follow the mesh.  ``params`` are moved to where the serve
        steps read them (``steps.serve_param_shardings``) once, here.

        ``spec_mode`` turns on speculative decoding: each engine step
        drafts ``spec_k`` tokens per slot (``"ngram"`` prompt-lookup,
        ``"draft"`` small model from ``configs.registry`` via
        ``draft_arch``, or an injected ``drafter``) and verifies them in
        one fused pass (``build_spec_verify_step``), emitting a variable
        1..spec_k+1 tokens per slot per step.  Greedy spec streams are
        bitwise-identical to plain greedy decode; rejected-draft K/V is
        rolled back via ``BlockAllocator.truncate`` on the paged path.
        ``spec_adaptive`` walks k along {2,4,8}∩[1,spec_k] by acceptance
        rate.  Dense (attention-only) families only.

        Robustness knobs (DESIGN.md §11): ``injector`` is a
        :class:`~repro.inference.faults.FaultInjector` consulted at the
        step hooks (poison/OOM/straggler); ``deadline_s`` is a default
        TTFT deadline in logical steps (per-request ``Request.deadline_s``
        tightens it) — expired never-admitted requests are shed;
        ``spec_autodisable_after`` > 0 degrades a slot to plain decode
        after that many consecutive zero-accept verify passes (0 = off;
        a verify-path fault always disables the slot's speculation)."""
        self.ap, self.cfg = ap, ap.cfg
        self.params = jax.device_put(params, serve_param_shardings(
            ap, ctx, mesh, fsdp_serve=fsdp_serve, device=device))
        self.slots = slots
        self.s_max = s_max
        self.ctx = ctx
        self.mesh = mesh
        # host-built arrays (slot state, block table) are uploaded here
        self._home = local_sharding(mesh, device)
        self.temperature = temperature
        self.top_k = top_k
        self.seed = seed
        self._rng = jax.random.PRNGKey(seed)
        if admit_mode not in ("full", "chunked"):
            raise ValueError(f"unknown admit_mode {admit_mode!r}")
        if admit_mode == "chunked" and self.cfg.family != "dense":
            raise ValueError("chunked admission needs an attention-only "
                             f"dense family, not {self.cfg.family!r}")
        if admit_mode == "chunked" and s_max % admit_chunk:
            # trailing-chunk pads would reach positions >= s_max; the paged
            # write path routes those to trash, but keep geometry exact
            raise ValueError(f"s_max={s_max} must be a multiple of "
                             f"admit_chunk={admit_chunk}")
        self.admit_mode = admit_mode
        self.admit_chunk = admit_chunk
        self.block_size = block_size
        # paging applies to the self-attention K/V only; attention-free
        # archs (rwkv) have fixed-size recurrent state and stay dense
        self.paged = block_size > 0 and not self.cfg.attn_free
        # -- prefix cache (DESIGN.md §14) -----------------------------------
        if prefix_cache not in ("off", "on"):
            raise ValueError(f"unknown prefix_cache {prefix_cache!r}")
        self.prefix_on = prefix_cache == "on"
        if self.prefix_on:
            if not self.paged:
                raise ValueError("prefix_cache needs the paged KV layout "
                                 "(block_size > 0, attention family) — "
                                 "sharing is per physical block")
            if self.cfg.family != "dense":
                raise ValueError("prefix_cache rides the chunked suffix-"
                                 "prefill path: dense families only, not "
                                 f"{self.cfg.family!r}")
            if kv_quant:
                raise ValueError("prefix_cache is incompatible with "
                                 "kv_quant (int8 cache is dense-layout, "
                                 "full-admission only)")
            if admit_chunk < 1 or admit_chunk % block_size:
                # a spliced prefix must end exactly where a chunk starts
                raise ValueError(f"admit_chunk={admit_chunk} must be a "
                                 f"positive multiple of "
                                 f"block_size={block_size} "
                                 "for prefix_cache")
            if s_max % admit_chunk:
                raise ValueError(f"s_max={s_max} must be a multiple of "
                                 f"admit_chunk={admit_chunk} for "
                                 "prefix_cache")
        if kv_quant:
            # the unsupported combinations all die deep inside jitted code
            # (prefill_chunk / init_cache asserts) — reject them here with
            # the actual reason instead
            if admit_mode == "chunked":
                raise ValueError("kv_quant needs full-prefill admission: "
                                 "chunked prefill cannot re-read the int8 "
                                 "cache mid-prompt")
            if self.paged:
                raise ValueError("kv_quant is incompatible with the paged "
                                 "KV layout (block_size > 0)")
            if spec_mode:
                raise ValueError("kv_quant is incompatible with "
                                 "speculative decoding (the verify pass "
                                 "rides chunked prefill)")
        self.kv_quant = kv_quant
        kw = dict(s_max=s_max, slots=slots, scan_layers=scan_layers,
                  fsdp_serve=fsdp_serve,
                  block_size=block_size if self.paged else 0,
                  kv_quant=kv_quant, n_blocks=n_blocks)
        self.alloc: Optional[BlockAllocator] = None
        if self.paged:
            max_blocks = paged_geometry(s_max, block_size)
            if n_blocks is None:
                kw["n_blocks"] = n_blocks = slots * max_blocks + 1
            self.alloc = BlockAllocator(n_blocks, block_size, slots,
                                        max_blocks)
        sample_kw = dict(temperature=temperature, top_k=top_k)
        self.cache = build_cache_init(
            ap, ctx, mesh, **{k: v for k, v in kw.items()
                              if k != "scan_layers"}).jit(
            **local_out(mesh, device))()
        self._serve = build_serve_step(ap, ctx, mesh, ar_table=ar_table,
                                       **sample_kw, **kw).jit()
        self._admit_kw = dict(ar_table=ar_table, **sample_kw, **kw)
        # -- speculative decoding wiring ------------------------------------
        self.spec_mode = spec_mode
        self.spec_k = spec_k
        self.drafter: Optional[Drafter] = None
        self._speck: Optional[AdaptiveK] = None
        self._spec_fns: Dict[int, Any] = {}     # k -> jitted verify step
        self._spec_kw = dict(self._admit_kw)
        self._spec_steps = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._spec_k_sum = 0
        if drafter is not None and not spec_mode:
            raise ValueError("an injected drafter needs spec_mode set "
                             "(got drafter= without spec_mode=)")
        if spec_mode:
            if self.cfg.family != "dense":
                raise ValueError("speculative decoding rides the chunked-"
                                 "prefill verify path: dense families "
                                 f"only, not {self.cfg.family!r}")
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            self.drafter = drafter if drafter is not None else \
                make_drafter(spec_mode, draft_arch=draft_arch, seed=seed)
            if spec_adaptive:
                self._speck = AdaptiveK(ks=tuple(sorted(
                    {k2 for k2 in (2, 4, 8) if k2 <= spec_k} | {spec_k})))
        self._admit_full: Dict[int, Any] = {}   # prompt_len -> jitted fn
        self._splice_fns: Dict[int, Any] = {}   # handoff len -> jitted fn
        self._admit_chunked = None
        if admit_mode == "chunked" or self.prefix_on:
            # final chunk samples the first token; intermediate chunks run
            # a logits-free executable (no vocab head / TP gather).  A
            # prefix-cache hit prefills its suffix through these even
            # under admit_mode="full" (chunked == full bitwise, so the
            # hit/miss paths emit identical tokens).
            self._admit_chunked = build_admit_chunk_step(
                ap, ctx, mesh, chunk=admit_chunk, **self._admit_kw).jit()
            self._admit_chunked_mid = build_admit_chunk_step(
                ap, ctx, mesh, chunk=admit_chunk, sample=False,
                **self._admit_kw).jit()
        self.prefix: Optional[PrefixCache] = None
        if self.prefix_on:
            self.prefix = PrefixCache(self.alloc, capacity=prefix_capacity)
        self._prefix_lookups = 0
        self._prefix_hits = 0
        self._prefix_tokens_saved = 0
        if self.paged:
            self._upload_table()

        # host mirrors of the device-side slot state
        self.positions = np.zeros((slots,), np.int32)
        self.remaining = np.zeros((slots,), np.int32)
        self.tokens = np.zeros((slots,), np.int32)
        self.active_mask = np.zeros((slots,), bool)
        # per-slot sampling chain: base key + tokens sampled so far (slot
        # s's next token draws with fold_in(slot_key[s], sample_idx[s]))
        self.slot_key = np.zeros((slots, 2), np.uint32)
        self.sample_idx = np.zeros((slots,), np.int32)
        self._admit_seq = np.full((slots,), -1, np.int64)  # admission order
        self._seq = 0
        self.active: List[Optional[Request]] = [None] * slots
        self.outputs: Dict[int, List[int]] = {}
        self._state = None       # device state dict (pushed lazily)
        self._dirty = True
        self._table_version = -1
        self.steps_run = 0
        self._wall0 = None
        self._wall_run = 0.0     # wall seconds of the last run(), at drain
        self._peak_occupied = 0  # max sum of live positions, in tokens
        self._kv_read = [0.0, 0]  # sum of per-step kv_read_share, steps
        self._requeue: List[Request] = []   # preempted, awaiting re-admission
        # evictions this run, counted at the batcher so the dense layout
        # reports them too (the allocator's counter only exists when
        # paged; the two agree on the paged path — asserted in metrics())
        self._preemptions = 0
        # -- robustness state (DESIGN.md §11) --------------------------------
        self.injector = injector
        self.deadline_s = deadline_s
        self.spec_autodisable_after = spec_autodisable_after
        self._quarantines = 0
        self._injected_oom = 0
        self._straggler_steps = 0
        self._spec_autodisables = 0
        self._shed: List[Request] = []      # shed this run (reported)
        self._wasted_tokens = 0
        self._oom_now = False               # injected burst, this step only
        self._spec_deny: set = set()        # rids degraded to plain decode
        self._spec_zero_acc = np.zeros((slots,), np.int64)
        self._tainted: set = set()          # slots holding injected NaN

    # -- state/device sync ---------------------------------------------------

    def _push_state(self):
        self._state = jax.device_put(
            {"tokens": self.tokens, "positions": self.positions,
             "remaining": self.remaining, "active": self.active_mask,
             "rng": self.slot_key, "sample_idx": self.sample_idx},
            self._home, may_alias=False)
        self._dirty = False

    def _upload_table(self):
        """The allocator's block table, put where the cache's table
        lives (a copy: the allocator edits its table in place)."""
        self.cache["block_tbl"] = jax.device_put(
            self.alloc.table, self.cache["block_tbl"].sharding,
            may_alias=False)

    def _sync_table(self):
        if self.alloc.version != self._table_version:
            self._upload_table()
            self._table_version = self.alloc.version

    def _step_rng(self):
        if self.temperature > 0.0:
            self._rng, r = jax.random.split(self._rng)
            return r
        return self._rng

    # -- admission -----------------------------------------------------------

    def _admit_fn(self, prompt_len: int):
        fn = self._admit_full.get(prompt_len)
        if fn is None:
            fn = build_admit_step(self.ap, self.ctx, self.mesh,
                                  prompt_len=prompt_len,
                                  **self._admit_kw).jit()
            self._admit_full[prompt_len] = fn
        return fn

    def _prefix_match(self, prompt: np.ndarray) -> List[int]:
        """Longest resident prefix of ``prompt``, truncated to what the
        suffix-prefill can actually skip: whole ``admit_chunk`` multiples
        (the spliced prefix must end exactly where a chunk starts) and at
        most ``S-1`` tokens (the final chunk always recomputes — it
        samples the first token).  Returns the physical blocks to splice.
        """
        S = int(prompt.shape[0])
        self._prefix_lookups += 1
        blocks = self.prefix.match(prompt)
        cap = ((S - 1) // self.admit_chunk) * self.admit_chunk
        shared = min(len(blocks) * self.block_size, cap)
        shared = (shared // self.admit_chunk) * self.admit_chunk
        return blocks[:shared // self.block_size]

    def _admit(self, slot: int, req: Request, now: float) -> bool:
        """Prefill one request into ``slot`` (on-device splice).  Returns
        False when the paged pool cannot hold the prompt right now.

        With the prefix cache on, admission first splices the longest
        resident prompt prefix (``share``: refcounts up, zero copies) and
        chunk-prefills only the suffix; after prefill, every fully-
        prompt-covered block is published back to the trie.  Ordering
        matters: the share happens *before* any trie reclaim, so
        allocation pressure can never evict the blocks just matched."""
        req.admitted_s = time.perf_counter()
        S = int(req.prompt.shape[0])
        if S + 1 > self.s_max:
            raise ValueError(f"prompt len {S} + 1 exceeds s_max={self.s_max}")
        chunks = -(-S // self.admit_chunk) \
            if self.admit_mode == "chunked" else 1
        with TraceAnnotation("serve.admit", rid=req.rid, chunks=chunks):
            shared_blocks: List[int] = []
            if self.prefix is not None:
                shared_blocks = self._prefix_match(req.prompt)
                if shared_blocks:
                    self.alloc.share(slot, shared_blocks)
            if self.alloc is not None:
                # +1: the first decode write lands at position S
                if self.prefix is not None \
                        and not self.alloc.can_allocate(slot, S + 1):
                    # cold trie blocks before live traffic: reclaim only what
                    # the growth is short (matched blocks are slot-referenced
                    # now — unevictable)
                    need = self.alloc.blocks_for(S + 1) - \
                        len(self.alloc.owned(slot))
                    self.prefix.reclaim(need - self.alloc.free_blocks)
                if not self.alloc.ensure(slot, S + 1):
                    if shared_blocks:
                        self.alloc.free(slot)   # drop just-taken references
                    return False
                with TraceAnnotation("serve.admit.table"):
                    self._sync_table()
            base = request_sampling_key(self.seed, req.rid)
            first = jax.random.fold_in(base, 0)   # token 0 of the chain
            with TraceAnnotation("serve.admit.chunks"):
                if shared_blocks:
                    n_shared = len(shared_blocks) * self.block_size
                    self._prefix_hits += 1
                    self._prefix_tokens_saved += n_shared
                    tok, self.cache = run_chunked_prefill(
                        self.params, self.cache, req.prompt, slot,
                        self.admit_chunk, self._admit_chunked_mid,
                        self._admit_chunked, self._rng, first,
                        start_chunk=n_shared // self.admit_chunk)
                elif self.admit_mode == "chunked":
                    tok, self.cache = run_chunked_prefill(
                        self.params, self.cache, req.prompt, slot,
                        self.admit_chunk, self._admit_chunked_mid,
                        self._admit_chunked, self._rng, first)
                else:
                    tok, self.cache = self._admit_fn(S)(
                        self.params, self.cache, jnp.asarray(req.prompt[None]),
                        jnp.int32(slot), first)
            if self.prefix is not None:
                # publish before activation (max_new == 1 releases the slot
                # inside _activate): every block whose positions are all
                # prompt is pinned — decode writes land at >= S, never here
                n_pub = S // self.block_size
                if n_pub:
                    self.prefix.insert(req.prompt,
                                       self.alloc.owned(slot)[:n_pub])
            with TraceAnnotation("serve.admit.first_token"):
                nxt = int(np.asarray(tok)[0])
            self._activate(slot, req, nxt, S, now, rng_key=base)
            return True

    def _activate(self, slot: int, req: Request, nxt: int, S: int,
                  now: float, rng_key=None) -> None:
        """Post-admission bookkeeping shared by local prefill admission and
        disaggregated handoff admission: the slot holds ``req`` at position
        ``S`` with first token ``nxt`` already emitted (sampled as token 0
        of the request's chain).  ``rng_key`` is that chain's base key —
        a handoff passes the bundle's; None recomputes from (seed, rid)."""
        if rng_key is None:
            rng_key = request_sampling_key(self.seed, req.rid)
        self.slot_key[slot] = np.asarray(rng_key, np.uint32)
        self.sample_idx[slot] = 1
        self.active[slot] = req
        self.positions[slot] = S
        self.remaining[slot] = req.max_new - 1
        self.tokens[slot] = nxt
        self.active_mask[slot] = True
        if self.drafter is not None:
            self.drafter.reset(slot, list(req.prompt) + [nxt])
        self._spec_zero_acc[slot] = 0   # collapse streak is per-occupant
        self._admit_seq[slot] = self._seq
        self._seq += 1
        self.outputs[req.rid] = [nxt]
        req.admit_step = int(now)
        req.first_token_s = time.perf_counter()
        self._dirty = True
        if self.remaining[slot] == 0:   # max_new == 1: prefill token only
            self._release(slot, now)

    def _splice_fn(self, n_tokens: int):
        fn = self._splice_fns.get(n_tokens)
        if fn is None:
            kw = {k: v for k, v in self._admit_kw.items()
                  if k in ("s_max", "slots", "block_size", "n_blocks",
                           "fsdp_serve")}
            fn = build_kv_splice_step(self.ap, self.ctx, self.mesh,
                                      n_tokens=n_tokens, **kw).jit()
            self._splice_fns[n_tokens] = fn
        return fn

    def admit_prefilled(self, slot: int, req: Request, bundle: KVBundle,
                        first_token: int, now: float) -> bool:
        """Disaggregated handoff admission: splice an imported KV bundle
        (canonical real-head layout, from another pool's prefill) into
        ``slot`` and activate the request with its already-sampled first
        token.  Returns False (no state change) when the paged pool cannot
        hold the context right now — the coordinator keeps it queued.
        Raises :class:`~repro.inference.kv_cache.BundleIntegrityError`
        (before any state change) when a sealed bundle's checksum does not
        match — in-flight corruption; the coordinator re-prefills."""
        req.admitted_s = time.perf_counter()
        bundle.verify()
        S = bundle.n_tokens
        if S + 1 > self.s_max:
            raise ValueError(f"handoff len {S} + 1 exceeds s_max="
                             f"{self.s_max}")
        if self.alloc is not None:
            # +1: the first decode write lands at position S
            if not self.alloc.ensure(slot, S + 1):
                return False
            self._sync_table()
        k = heads_to_slots(bundle.k, self.ap.gqa.kv_map)[:, None]
        v = heads_to_slots(bundle.v, self.ap.gqa.kv_map)[:, None]
        self.cache = self._splice_fn(S)(
            self.cache, jnp.asarray(k), jnp.asarray(v), jnp.int32(slot))
        # continue the *prefill pool's* sampling chain (bundle.rng); a
        # greedy-only producer leaves it None and _activate recomputes
        self._activate(slot, req, int(first_token), S, now,
                       rng_key=bundle.rng)
        return True

    def _release(self, slot: int, now: float):
        req = self.active[slot]
        req.done_s = time.perf_counter()
        req.done_step = int(now)
        req.output = np.asarray(self.outputs[req.rid], np.int32)
        self.active[slot] = None
        self.active_mask[slot] = False
        self.remaining[slot] = 0
        self.sample_idx[slot] = 0
        self._admit_seq[slot] = -1
        if self.alloc is not None:
            self.alloc.free(slot)
            self._sync_table()
        self._dirty = True

    # -- preemption / eviction ----------------------------------------------

    def _evict(self, slot: int) -> None:
        """Evict ``slot``'s request and requeue it for recompute-from-
        scratch — shared by capacity preemption, injected-OOM bursts and
        non-finite-logits quarantine.  The recompute replays the request's
        stateless sampling chain, so its final tokens are bitwise-identical
        to an uneventful run (``request_sampling_key``)."""
        if slot in self._tainted:
            self._scrub_slot(slot)
        req = self.active[slot]
        req.preempted += 1
        self._preemptions += 1
        self._wasted_tokens += len(self.outputs[req.rid])
        del self.outputs[req.rid]
        self.active[slot] = None
        self.active_mask[slot] = False
        self.remaining[slot] = 0
        self._admit_seq[slot] = -1
        if self.alloc is not None:
            self.alloc.preempt(slot)
            self._sync_table()
        self._requeue.append(req)
        self._dirty = True

    def _preempt_youngest(self) -> bool:
        """Evict the most recently admitted active request (vLLM-style
        last-come-first-preempted), requeue it for recompute-from-scratch.
        Returns False when nothing is evictable."""
        live = [s for s in range(self.slots) if self.active_mask[s]]
        if not live:
            return False
        self._evict(max(live, key=lambda s: self._admit_seq[s]))
        return True

    def _quarantine(self, slot: int) -> None:
        """Non-finite logits in ``slot``: its emitted token this step is
        garbage, so drop the step's output for the slot, evict it, and let
        the requeue path recompute the request exactly (same machinery as
        capacity preemption).  Under spec decode the slot's speculation is
        also permanently degraded — a drafter feeding on poisoned history
        is not trusted again."""
        self._quarantines += 1
        if self.spec_mode:
            rid = self.active[slot].rid
            if rid not in self._spec_deny:
                self._spec_deny.add(rid)
                self._spec_autodisables += 1
        self._evict(slot)

    def _poison_slot(self, slot: int) -> None:
        """Injected fault: poke NaN into the slot's most recently written
        K position, so the *device* step produces non-finite logits and
        the ``finite`` guard must catch them.  Safe by the write-ordering
        invariant: after quarantine + re-admission, every position up to
        the new write frontier is overwritten before it is read again."""
        p = max(int(self.positions[slot]) - 1, 0)
        nan = jnp.asarray(jnp.nan, self.cache["k"].dtype)
        if self.paged:
            bidx = p // self.block_size
            if not self.alloc.is_exclusive(slot, bidx):
                # shared/held block (a prompt block the trie or a sharer
                # still reads): copy-on-write before the divergent poke —
                # fault injection must corrupt only its victim.  If no
                # free block exists even after trie reclaim, skip the
                # injection entirely (never corrupt a neighbour).
                if not self.alloc.free_blocks and self.prefix is not None:
                    self.prefix.reclaim(1)
                if not self.alloc.free_blocks:
                    return
                old, new = self.alloc.fork_for_write(slot, bidx)
                self.cache["k"] = self.cache["k"].at[:, new].set(
                    self.cache["k"][:, old])
                self.cache["v"] = self.cache["v"].at[:, new].set(
                    self.cache["v"][:, old])
                self._sync_table()
            phys = int(self.alloc.table[slot, bidx])
            off = p % self.block_size
            self.cache["k"] = self.cache["k"].at[:, phys, off].set(nan)
        else:
            self.cache["k"] = self.cache["k"].at[:, slot, p].set(nan)
        self._tainted.add(slot)

    def _scrub_slot(self, slot: int) -> None:
        """Zero a tainted slot's K/V storage before its blocks are freed.

        The poisoned step itself writes NaN K *and* V at the then-current
        position in every layer past the first (projections of the NaN
        hidden state), and a masked attention lane still contributes
        ``0 * NaN = NaN`` through the V-weighted sum — so without this
        scrub a freed contaminated block re-poisons its next occupant at
        positions past that occupant's write frontier.  Zeros are safe on
        both sides of the mask: masked lanes contribute exactly 0 and
        unmasked positions are rewritten before they are read (the same
        write-ordering invariant recompute-from-scratch relies on)."""
        if self.paged:
            # exclusively-owned blocks only: a slot never writes a shared
            # or trie-held block (fork-before-write, _poison_slot), so
            # contamination is confined to its private blocks — and a
            # shared block holds live sharers' clean prompt K/V that a
            # zero-fill would destroy mid-read.
            blocks = [b for i, b in enumerate(self.alloc.owned(slot))
                      if self.alloc.is_exclusive(slot, i)]
            if blocks:
                idx = jnp.asarray(blocks, jnp.int32)
                self.cache["k"] = self.cache["k"].at[:, idx].set(0)
                self.cache["v"] = self.cache["v"].at[:, idx].set(0)
        else:
            self.cache["k"] = self.cache["k"].at[:, slot].set(0)
            self.cache["v"] = self.cache["v"].at[:, slot].set(0)
        self._tainted.discard(slot)

    def _ensure_growth(self, slot: int,
                       n_tokens: Optional[int] = None) -> None:
        """Pre-step invariant: blocks cover the next write position (or an
        explicit ``n_tokens`` target — the spec verify chunk's whole write
        range).  On OOM, preempt youngest-first until the growth fits (the
        growing slot itself may be the victim).  Under an injected OOM
        burst any *real* growth is denied instead: the growing slot itself
        is evicted (surgical — neighbours keep their blocks) and recomputed
        once the burst passes."""
        if n_tokens is None:
            n_tokens = int(self.positions[slot]) + 1
        if self._oom_now and self.alloc.needs_growth(slot, n_tokens):
            self._injected_oom += 1
            self._evict(slot)
            return
        while not self.alloc.ensure(slot, n_tokens):
            if self.prefix is not None:
                # cold trie blocks go before live traffic: evict LRU
                # unreferenced nodes first, preempt a request only when
                # the trie has nothing left to give
                need = self.alloc.blocks_for(n_tokens) - \
                    len(self.alloc.owned(slot))
                if self.prefix.reclaim(need - self.alloc.free_blocks) > 0:
                    continue
            victim_ok = self._preempt_youngest()
            if not self.active_mask[slot]:
                return  # we evicted ourselves
            if not victim_ok:
                raise RuntimeError(
                    "paged KV pool cannot hold a single request; "
                    "raise n_blocks")
        self._sync_table()

    def _pre_step_faults(self, now: float) -> None:
        """Consult the injector's decode-path hooks for this step: arm the
        OOM burst (read by ``_ensure_growth``), poison any chosen live
        slots, and apply the straggler's wall-clock delay (logical clock
        untouched)."""
        inj = self.injector
        self._oom_now = False
        if inj is None:
            return
        self._oom_now = inj.oom_burst(now)
        for s in range(self.slots):
            if not self.active_mask[s]:
                continue
            req = self.active[s]
            if inj.poison_slot(req.rid, len(self.outputs[req.rid])):
                self._poison_slot(s)
        d = inj.straggle(now)
        if d >= 0.0:
            self._straggler_steps += 1
            if d > 0.0:
                time.sleep(d)

    # -- speculative decoding ------------------------------------------------

    def _spec_fn(self, k: int):
        fn = self._spec_fns.get(k)
        if fn is None:
            fn = build_spec_verify_step(self.ap, self.ctx, self.mesh,
                                        k=k, **self._spec_kw).jit()
            self._spec_fns[k] = fn
        return fn

    def _spec_step(self, now: float):
        """One draft + fused-verify step over all slots.

        Per active slot: draft k tokens, write/score the C = k+1 chunk
        [current token, drafts] in one pass, take the verified prefix plus
        one correction/bonus token (1..k+1 tokens), and truncate the
        rejected tail's blocks back to the pool.  The host slot state is
        authoritative (variable per-slot advance), re-pushed every step.

        Degraded mode: slots whose rid is in ``_spec_deny`` (verify-path
        fault, or ``spec_autodisable_after`` consecutive zero-accept
        passes) draft nothing and take at most the correction token —
        per-slot plain decode riding the same verify executable, still
        emitting exact target-model tokens.  If *every* active slot is
        denied the whole step falls back to the plain executable.
        """
        if not self.active_mask.any():
            return
        self._pre_step_faults(now)
        if not self.active_mask.any():   # faults evicted every slot
            return
        denied = np.array([self.active_mask[s]
                           and self.active[s].rid in self._spec_deny
                           for s in range(self.slots)])
        if denied[self.active_mask].all():
            return self._plain_step(now, faults_done=True)
        k = self._speck.k if self._speck is not None else self.spec_k
        C = k + 1
        drafts = np.zeros((self.slots, k), np.int32)
        for s in range(self.slots):
            if self.active_mask[s] and not denied[s]:
                # clamp: a cross-vocabulary drafter must still propose
                # valid target ids (bad ids would just be rejected anyway)
                drafts[s] = np.clip(self.drafter.draft(s, k), 0,
                                    self.cfg.vocab_size - 1)
        if self.alloc is not None:
            for s in range(self.slots):
                # the verify chunk writes positions [p, p+C); cover them
                # all up front (clamped to capacity: overflow writes are
                # trash-routed on device), preempting youngest on OOM
                if self.active_mask[s]:
                    self._ensure_growth(s, min(int(self.positions[s]) + C,
                                               self.s_max))
        occ = int(self.positions[self.active_mask].sum()) + \
            int(self.active_mask.sum())
        self._peak_occupied = max(self._peak_occupied, occ)
        if self._dirty:
            self._push_state()
        was_active = self.active_mask.copy()
        # the verify step keeps the lean 4-field state (its sampled mode
        # draws from the step-level rng, not the per-slot chains)
        spec_state = {k2: self._state[k2] for k2 in
                      ("tokens", "positions", "remaining", "active")}
        emitted, accepted, finite, self.cache = self._spec_fn(k)(
            self.params, self.cache, spec_state, jnp.asarray(drafts),
            self._step_rng())
        emitted = np.asarray(emitted)
        accepted = np.asarray(accepted)
        finite = np.asarray(finite)
        self.steps_run += 1
        self._spec_steps += 1
        self._spec_k_sum += k
        n_active = acc_sum = 0
        for s in range(self.slots):
            if not was_active[s]:
                continue
            if not finite[s]:
                self._quarantine(s)
                continue
            a = int(accepted[s])
            # cap by the request budget and the cache capacity — exactly
            # where sequential decode would have stopped emitting.  The
            # capacity floor of 1 mirrors the plain step: a slot admitted
            # at position s_max-1 still decodes once (querying/writing the
            # last in-bounds position) before its done check fires.
            take = min(a + 1, int(self.remaining[s]),
                       max(self.s_max - 1 - int(self.positions[s]), 1))
            if denied[s]:
                take = min(take, 1)   # degraded: correction token only
            toks = [int(t) for t in emitted[s, :take]]
            self.outputs[self.active[s].rid].extend(toks)
            self.tokens[s] = toks[-1]
            self.positions[s] += take
            self.remaining[s] -= take
            if not denied[s]:
                self.drafter.observe(s, toks)
                n_active += 1
                acc_sum += a
                self._spec_drafted += k
                self._spec_accepted += a
                if self.spec_autodisable_after > 0:
                    # acceptance collapse: N consecutive all-reject passes
                    # mean drafting is pure overhead for this slot
                    self._spec_zero_acc[s] = 0 if a else \
                        self._spec_zero_acc[s] + 1
                    if self._spec_zero_acc[s] >= \
                            self.spec_autodisable_after:
                        self._spec_deny.add(self.active[s].rid)
                        self._spec_autodisables += 1
            if self.alloc is not None:
                # KV rollback: blocks holding only rejected-draft writes
                # go back to the pool
                self.alloc.truncate(s, int(self.positions[s]))
                self.alloc.note_usage(s, int(self.positions[s]))
            if self.remaining[s] <= 0 \
                    or self.positions[s] >= self.s_max - 1:
                self._release(s, now)
        if self.alloc is not None:
            self._sync_table()
        self._dirty = True  # host state is authoritative under spec
        if self._speck is not None and n_active:
            self._speck.update(acc_sum / n_active, k)

    # -- one engine step -----------------------------------------------------

    def step(self, now: float):
        """One decode step over all slots (no-op when none active)."""
        with TraceAnnotation("serve.step"):
            if self.spec_mode:
                return self._spec_step(now)
            return self._plain_step(now)

    def _plain_step(self, now: float, faults_done: bool = False):
        """One plain decode step (``faults_done``: the spec path already
        ran this step's fault hooks before falling back here)."""
        if not self.active_mask.any():
            return
        with TraceAnnotation("serve.step.prepare"):
            if not faults_done:
                self._pre_step_faults(now)
                if not self.active_mask.any():   # faults evicted every slot
                    return
            if self.alloc is not None:
                for s in range(self.slots):
                    # growth only at block boundaries: next write position
                    # is positions[s], covered unless it opens a fresh block
                    if self.active_mask[s] \
                            and self.positions[s] % self.block_size == 0:
                        self._ensure_growth(s)
                if not self.active_mask.any():   # OOM burst evicted them all
                    return
            occ = int(self.positions[self.active_mask].sum()) + \
                int(self.active_mask.sum())
            self._peak_occupied = max(self._peak_occupied, occ)
            if self.paged:
                self._count_kv_read()
            if self._dirty:
                self._push_state()
        was_active = self.active_mask.copy()
        with TraceAnnotation("serve.step.dispatch"):
            emitted, done, finite, self._state, self.cache = self._serve(
                self.params, self.cache, self._state)
        with TraceAnnotation("serve.step.readback"):
            emitted = np.asarray(emitted)
            done = np.asarray(done)
            finite = np.asarray(finite)
        self.steps_run += 1
        with TraceAnnotation("serve.step.commit"):
            for s in range(self.slots):
                if not was_active[s]:
                    continue
                if not finite[s]:
                    # drop this step's garbage token and recompute the
                    # whole request through the preemption path — exact
                    # replay
                    self._quarantine(s)
                    continue
                self.outputs[self.active[s].rid].append(int(emitted[s]))
                self.tokens[s] = emitted[s]
                self.positions[s] += 1
                self.remaining[s] -= 1
                self.sample_idx[s] += 1
                if self.alloc is not None:
                    self.alloc.note_usage(s, int(self.positions[s]))
                if done[s]:
                    self._release(s, now)

    def _count_kv_read(self) -> None:
        """The blocks this plain step's paged attention reads, each live
        slot's ``ceil((pos + 1) / block_size)``, as a share of the
        ``slots * max_blocks`` that gathering the whole table reads."""
        live = self.positions[self.active_mask] + 1
        read = int((-(-live // self.block_size)).sum())
        self._kv_read[0] += read / (self.slots * self.s_max
                                    // self.block_size)
        self._kv_read[1] += 1

    # -- trace replay --------------------------------------------------------

    def reset_run_stats(self) -> None:
        """Reset per-run accounting (step counts, spec counters, allocator
        trace stats) so :meth:`metrics` reflects one trace only.  Called by
        :meth:`run` on a drained batcher, and by an external driver
        (``inference.disagg.DisaggCoordinator``) that steps the batcher
        itself; current slot ownership is untouched."""
        self.steps_run = 0
        self._peak_occupied = 0
        self._kv_read = [0.0, 0]
        self.outputs = {}
        self._spec_steps = self._spec_drafted = 0
        self._spec_accepted = self._spec_k_sum = 0
        self._quarantines = self._injected_oom = 0
        self._straggler_steps = self._spec_autodisables = 0
        self._wasted_tokens = 0
        self._preemptions = 0
        # per-run prefix counters only — the trie itself persists across
        # runs (warm cross-trace reuse is the feature)
        self._prefix_lookups = 0
        self._prefix_hits = 0
        self._prefix_tokens_saved = 0
        self._shed = []
        self._spec_deny = set()
        self._spec_zero_acc[:] = 0
        self._tainted = set()
        if self.injector is not None:
            self.injector.reset_stats()
        if self.drafter is not None:
            self.drafter.calls = self.drafter.hits = 0
        if self.alloc is not None:
            self.alloc.reset_stats()
        self._wall0 = time.perf_counter()

    def _shed_req(self, req: Request, now: float, reason: str) -> None:
        """Drop a never-admitted request, *reporting* it (shed_reason /
        metrics counter) — shedding is load control, not silent loss."""
        req.shed_step = int(now)
        req.shed_reason = reason
        self._shed.append(req)

    def _deadline(self, req: Request) -> float:
        """Effective TTFT deadline (logical steps): the tighter of the
        request's own and the batcher default."""
        d = req.deadline_s
        if self.deadline_s is not None:
            d = min(d, self.deadline_s)
        return d

    def tick(self, arrived: List[Request], now: float) -> None:
        """One logical scheduling tick over an externally-owned queue of
        due arrivals (mutated in place): shed deadline-expired entries,
        admit (preempted requeue first, then arrivals, FCFS), and run one
        engine step.  ``run`` drives this on its private queue; an
        external driver (``inference.router.Router``) owns a per-replica
        queue and calls this directly — one code path, so a routed
        replica schedules exactly like a standalone batcher."""
        with TraceAnnotation("serve.tick"):
            expired = [r for r in arrived
                       if now - r.arrival_s > self._deadline(r)]
            for r in expired:
                self._shed_req(r, now, "deadline")
                arrived.remove(r)
            # admit preempted requests first, then due arrivals
            for s in range(self.slots):
                if self.active[s] is not None:
                    continue
                if self._requeue:
                    if self._admit(s, self._requeue[0], now):
                        self._requeue.pop(0)
                    continue
                if arrived:
                    if self._admit(s, arrived[0], now):
                        arrived.pop(0)
            self.step(now)

    def drained(self, arrived: List[Request]) -> bool:
        """No queued, requeued, or active work left for this batcher."""
        return not arrived and not self._requeue \
            and all(a is None for a in self.active)

    def run(self, requests: List[Request],
            max_steps: int = 100000) -> List[Request]:
        """Replay a trace (requests sorted by arrival) to completion.

        Deadline shedding: a request still waiting for *first* admission
        past its effective deadline (:meth:`_deadline`) is shed instead of
        served.  Preempted requests are never shed — their first token was
        already promised and the recompute replays it exactly."""
        waiting = sorted(requests, key=lambda r: r.arrival_s)
        qi = 0
        now = 0.0
        arrived: List[Request] = []   # due, never admitted
        if not self.active_mask.any() and not self._requeue:
            # fresh replay on a drained batcher
            self.reset_run_stats()
        self._wall0 = time.perf_counter()
        for _ in range(max_steps):
            while qi < len(waiting) and waiting[qi].arrival_s <= now:
                arrived.append(waiting[qi])
                qi += 1
            if qi >= len(waiting) and self.drained(arrived):
                break
            self.tick(arrived, now)
            now += 1.0  # logical step clock
        self._wall_run = time.perf_counter() - self._wall0
        return requests

    # -- metrics -------------------------------------------------------------

    def defragment(self):
        """Compact the physical block pool (paged only); applies the block
        permutation to the device cache and uploads the rewritten table."""
        if self.alloc is None:
            return
        perm = self.alloc.defragment()
        if perm is None:
            return
        p = jnp.asarray(perm)
        self.cache["k"] = jnp.take(self.cache["k"], p, axis=1)
        self.cache["v"] = jnp.take(self.cache["v"], p, axis=1)
        self._sync_table()

    def op_scopes(self) -> Dict[str, List[Dict[str, str]]]:
        """The model scope (``hlo_analysis.SCOPES``) of each instruction of
        the executables that serve this batcher: the plain decode step
        and, with chunked admission, the middle- and final-chunk
        executables, each built, lowered and compiled anew on the
        arguments it runs with (the same program, so the same instruction
        names).  Keyed by module name as a profiler trace gives it, one map
        per executable: both chunk executables are ``admit_chunk``.  Call
        it outside a measured window."""
        if self._state is None:
            self._push_state()
        kw = self._admit_kw
        runs = [(build_serve_step(self.ap, self.ctx, self.mesh, **kw),
                 (self.params, self.cache, self._state))]
        if self._admit_chunked is not None:
            tok = jax.ShapeDtypeStruct((1, self.admit_chunk), jnp.int32)
            i32 = jax.ShapeDtypeStruct((), jnp.int32)
            args = (self.params, self.cache, tok, tok, i32, i32, self._rng)
            runs += [(build_admit_chunk_step(
                self.ap, self.ctx, self.mesh, chunk=self.admit_chunk,
                sample=sample, **kw), args) for sample in (False, True)]
        out: Dict[str, List[Dict[str, str]]] = {}
        # fresh executables, and the metadata in the persistent cache's
        # key: an executable compiled before, or an entry keyed without
        # metadata (the default), may carry another build's op names
        keyed = jax.config.jax_compilation_cache_include_metadata_in_key
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        try:
            for built, args in runs:
                text = built.jit().lower(*args).compile().as_text()
                out.setdefault(module_name(text), []).append(
                    op_scopes(text))
        finally:
            jax.config.update(
                "jax_compilation_cache_include_metadata_in_key", keyed)
        return out

    def metrics(self, requests: List[Request]) -> ServeMetrics:
        done = [r for r in requests if r.output is not None]
        wall = self._wall_run   # captured at run() drain, not call time
        total_new = sum(len(r.output) for r in done)
        # TTFT: queueing wait + the admission (prefill) tick.
        ttft = [max(r.admit_step - r.arrival_s, 0.0) + 1.0 for r in done]
        # TPOT over decode tokens only: a request admitted at step t decodes
        # at steps t..done_step inclusive (admission and the first decode
        # share a logical tick), i.e. done-admit+1 steps for len-1 tokens.
        tpot = [(r.done_step - r.admit_step + 1) / (len(r.output) - 1)
                for r in done if len(r.output) > 1]
        if self.alloc is not None:
            st = self.alloc.stats()
            # peak footprint a right-sized deployment would have to reserve
            peak_tok = st.peak_used_blocks * st.block_size
            cap = (st.n_blocks - 1) * st.block_size
            util = self._peak_occupied / peak_tok if peak_tok else 0.0
            # every eviction goes through _evict -> alloc.preempt, so the
            # two counters can only disagree on a bookkeeping bug
            assert st.preemptions == self._preemptions, \
                (st.preemptions, self._preemptions)
            cache_stats = st.to_dict()
        else:
            # dense reserves worst case up front regardless of occupancy
            peak_tok = cap = self.slots * self.s_max
            util = self._peak_occupied / cap if cap else 0.0
            cache_stats = None
        # counted at the batcher, not the allocator: dense-layout
        # evictions (quarantine / injected OOM) used to report as 0
        preempt = self._preemptions
        return ServeMetrics(
            requests=len(requests), completed=len(done),
            total_new_tokens=total_new, steps=self.steps_run, wall_s=wall,
            throughput_tok_s=total_new / wall if wall > 0 else 0.0,
            ttft_steps_p50=_percentile(ttft, 50),
            ttft_steps_p99=_percentile(ttft, 99),
            tpot_steps_p50=_percentile(tpot, 50),
            tpot_steps_p99=_percentile(tpot, 99),
            preemptions=preempt, peak_kv_tokens=int(peak_tok),
            kv_capacity_tokens=int(cap), cache_utilization=float(util),
            kv_read_share=self._kv_read[0] / self._kv_read[1]
            if self._kv_read[1] else 0.0,
            cache_stats=cache_stats,
            spec_steps=self._spec_steps,
            drafted_tokens=self._spec_drafted,
            accepted_tokens=self._spec_accepted,
            acceptance_rate=self._spec_accepted / self._spec_drafted
            if self._spec_drafted else 0.0,
            accepted_tokens_per_step=self._spec_accepted / self._spec_steps
            if self._spec_steps else 0.0,
            drafter_hit_rate=self.drafter.hit_rate
            if self.drafter is not None else 0.0,
            spec_k_mean=self._spec_k_sum / self._spec_steps
            if self._spec_steps else 0.0,
            quarantines=self._quarantines,
            injected_oom=self._injected_oom,
            shed_requests=len(self._shed),
            spec_autodisables=self._spec_autodisables,
            straggler_steps=self._straggler_steps,
            wasted_tokens=self._wasted_tokens,
            prefix_lookups=self._prefix_lookups,
            prefix_hits=self._prefix_hits,
            prefix_tokens_saved=self._prefix_tokens_saved,
            prefix_hit_rate=self._prefix_hits / self._prefix_lookups
            if self._prefix_lookups else 0.0,
            ttft_steps_samples=ttft, tpot_steps_samples=tpot)


def make_trace(n_requests: int, *, mean_in: int, mean_out: int,
               rate: float, burstiness: float = 2.0, vocab: int = 97,
               seed: int = 0) -> List[Request]:
    """BurstGPT-style synthetic trace: gamma inter-arrivals (shape=1/CV^2 ~
    burstiness), lognormal-ish lengths (paper Appendix C.4.2)."""
    rng = np.random.default_rng(seed)
    shape = 1.0 / burstiness
    gaps = rng.gamma(shape, scale=1.0 / (rate * shape), size=n_requests)
    arrivals = np.cumsum(gaps)
    reqs = []
    for i in range(n_requests):
        s_in = max(8, int(rng.lognormal(np.log(mean_in), 0.6)) // 8 * 8)
        s_out = max(1, int(rng.lognormal(np.log(mean_out), 0.6)))
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, vocab, s_in).astype(np.int32),
            max_new=s_out, arrival_s=float(arrivals[i])))
    return reqs


def make_prefix_trace(n_requests: int, *, prefix_len: int,
                      shared_frac: float, mean_in: int, mean_out: int,
                      rate: float, burstiness: float = 2.0, vocab: int = 97,
                      seed: int = 0,
                      clip_len: Optional[int] = None) -> List[Request]:
    """A :func:`make_trace` trace where a ``shared_frac`` fraction of
    requests open with one common ``prefix_len``-token system prompt —
    the multi-tenant shared-prefix workload the prefix cache targets.

    Sharing is decided by a fixed-seed per-request uniform draw against
    the threshold, so raising ``shared_frac`` only *adds* shared requests
    (never reshuffles which) — the monotonicity ``bench_prefix`` gates
    on.  ``clip_len`` caps total prompt length (trim the unique tail)
    so prefixed prompts still fit ``s_max - 1``.
    """
    rng = np.random.default_rng(seed + 0x5afe)
    shared = rng.integers(0, vocab, prefix_len).astype(np.int32)
    u = rng.random(n_requests)
    reqs = make_trace(n_requests, mean_in=mean_in, mean_out=mean_out,
                      rate=rate, burstiness=burstiness, vocab=vocab,
                      seed=seed)
    for i, r in enumerate(reqs):
        if u[i] < shared_frac:
            r.prompt = np.concatenate([shared, r.prompt]).astype(np.int32)
        if clip_len is not None and r.prompt.shape[0] > clip_len:
            r.prompt = r.prompt[:clip_len]
    return reqs


__all__ = ["ContinuousBatcher", "Request", "ServeMetrics", "make_trace",
           "make_prefix_trace", "run_chunked_prefill",
           "request_sampling_key"]
