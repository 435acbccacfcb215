"""Disaggregated prefill/decode serving: two pools, two operating points.

Prefill and decode stress the network in opposite ways (paper Sec. 3.5 and
the communication characterizations in PAPERS.md): prefill is compute-bound
with *large* per-layer all-reduce messages (prompt-length x d_model), while
decode is latency-bound on *small* per-token all-reduces — exactly the
128 KB-2 MB regime where the paper's strategy choice (hierarchical RD vs
ring) matters most.  A colocated deployment forces one mesh layout and one
``ar_table`` operating point onto both phases; this module splits them:

* a :class:`PrefillPool` runs prompt prefills only (its own ``tp``/pods
  mesh, its own AR dispatch table) and emits each finished context as a
  layout-neutral :class:`~repro.inference.kv_cache.KVBundle` plus the
  already-sampled first token;
* a decode-side :class:`~repro.inference.scheduler.ContinuousBatcher`
  (again its own mesh + table) imports bundles via
  ``ContinuousBatcher.admit_prefilled`` — resharding between the pools'
  GQA slot layouts happens in the bundle pack/unpack
  (``kv_cache.slots_to_heads`` / ``heads_to_slots``), so the pools' TP
  degrees are fully independent;
* the :class:`DisaggCoordinator` is the router in between: it admits
  arrivals to the prefill pool, moves completed contexts (KV bundle +
  first token + position state) across the handoff queue into free decode
  slots, routes decode-pool preemptions *back* to the prefill pool for
  recompute, and tracks queue depths / transfer bytes / per-pool AR
  message-size buckets.

Correctness bar (enforced by tests/test_disagg.py and, under a fault
plan, tests/test_faults.py): a disaggregated greedy trace is **bitwise
equal** to the colocated paged serve of the same trace, including with
speculative decoding enabled on the decode pool — a slot's greedy tokens
depend only on its own prompt and KV, and the handoff round-trips KV
without dtype conversion.  Sampled (temperature > 0) plain-decode traces
are **token-identical** too: every request samples from its own stateless
key chain (``scheduler.request_sampling_key``), whose base key travels
with the context in ``KVBundle.rng`` (PR 5 closed the per-pool-RNG gap).

Scheduling model: the coordinator shares the batcher's logical step clock
(1.0 per tick).  Each tick the prefill pool processes up to
``prefill_per_step`` queued prompts, the handoff queue drains into free
decode slots, and the decode pool runs one (plain or spec-verify) step.
TTFT is attributed to the prefill pool + transfer wait; TPOT to the
decode pool (DESIGN.md §9).

Known gaps: dense (attention-only) families only — recurrent state
handoff is not implemented (same restriction as chunked prefill / spec
decode); *speculative* sampled streams still draw their accept/resample
randomness from the step-level rng, so spec + temperature > 0 is
seed-deterministic but not colocated-identical (plain sampled decode is);
the handoff moves bundles through host memory (one device round-trip),
standing in for a NIC/ICI transport.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import autotune
from ..core.pcontext import ParallelCtx, LOCAL
from ..parallel.steps import (build_admit_chunk_step, build_cache_init,
                              build_prefill_only_step, serve_param_shardings)
from .faults import FaultInjector
from .kv_cache import (BundleIntegrityError, KVBundle, export_slot,
                       slots_to_heads)
from .scheduler import (ContinuousBatcher, Request, _percentile,
                        local_out, request_sampling_key, run_chunked_prefill)


def pool_tuner(ar_table) -> autotune.AutoTuner:
    """Resolve a pool-private dispatch table: an AutoTuner instance or a
    path resolves via :func:`autotune.tuner_for`; None seeds a fresh
    analytic table instead of sharing the process-wide one.  Each pool
    owning its tuner is what makes per-pool AR dispatch observable (the
    tuner records the message-size buckets its pool keyed on) — which is
    also why a missing table path is an error here rather than the
    colocated builders' silent fallback: falling back to the shared
    process-wide tuner would merge both pools' lookup logs."""
    if isinstance(ar_table, str) and not os.path.exists(ar_table):
        raise FileNotFoundError(f"pool ar_table not found: {ar_table!r}")
    if ar_table is not None:
        return autotune.tuner_for(ar_table)
    base = autotune.active()
    return autotune.AutoTuner(base.net)


class PrefillPool:
    """Prefill-only serving pool: prompt in, (first token, KVBundle) out.

    ``admit_mode="full"`` runs one ``build_prefill_only_step`` executable
    per distinct prompt length and packs the bundle straight from the
    returned states; ``"chunked"`` feeds the prompt through the fixed-size
    chunked-prefill executables into a private 1-slot cache (recompile-
    free; ``block_size`` > 0 exercises the paged write path) and exports
    from the cache.  Both paths produce identical bundles.
    """

    def __init__(self, ap, params, *, s_max: int, ctx: ParallelCtx = LOCAL,
                 mesh=None, ar_table=None, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0, scan_layers: bool = True,
                 fsdp_serve: bool = False, admit_mode: str = "full",
                 admit_chunk: int = 32, block_size: int = 0,
                 device=None):
        self.ap, self.cfg = ap, ap.cfg
        # weights (and the private cache) where the steps read them, as in
        # ContinuousBatcher
        self.params = jax.device_put(params, serve_param_shardings(
            ap, ctx, mesh, fsdp_serve=fsdp_serve, device=device))
        if self.cfg.family != "dense":
            raise ValueError("disaggregated serving supports attention-"
                             f"only dense families, not "
                             f"{self.cfg.family!r}")
        if admit_mode not in ("full", "chunked"):
            raise ValueError(f"unknown admit_mode {admit_mode!r}")
        if admit_mode == "chunked" and s_max % admit_chunk:
            raise ValueError(f"s_max={s_max} must be a multiple of "
                             f"admit_chunk={admit_chunk}")
        self.s_max = s_max
        self.ctx = ctx
        self.mesh = mesh
        self.temperature = temperature
        self.top_k = top_k
        self.admit_mode = admit_mode
        self.admit_chunk = admit_chunk
        self.block_size = block_size
        self.tuner = pool_tuner(ar_table)
        self.seed = seed
        self._rng = jax.random.PRNGKey(seed)
        self._step_kw = dict(scan_layers=scan_layers,
                             fsdp_serve=fsdp_serve,
                             temperature=temperature, top_k=top_k,
                             ar_table=self.tuner)
        self._full_fns: Dict[int, Any] = {}    # prompt_len -> jitted fn
        self.cache = None
        if admit_mode == "chunked":
            # private 1-slot cache; n_blocks=None -> identity block table
            # at full capacity, so no allocator is needed (one request at
            # a time, overwritten in place)
            geo = dict(slots=1, s_max=s_max, block_size=block_size,
                       n_blocks=None, fsdp_serve=fsdp_serve)
            self.cache = build_cache_init(ap, ctx, mesh, **geo).jit(
                **local_out(mesh, device))()
            kw = dict(self._step_kw)
            kw.update(slots=1, s_max=s_max, block_size=block_size,
                      n_blocks=None)
            self._chunk_final = build_admit_chunk_step(
                ap, ctx, mesh, chunk=admit_chunk, **kw).jit()
            self._chunk_mid = build_admit_chunk_step(
                ap, ctx, mesh, chunk=admit_chunk, sample=False, **kw).jit()
            if block_size > 0:
                self._table_row = 1 + np.arange(s_max // block_size,
                                                dtype=np.int32)
        # trace-scoped stats
        self.prefills = 0
        self.prompt_tokens = 0
        self.wall_s = 0.0
        self.analytic_buckets: set = set()

    def _full_fn(self, prompt_len: int):
        fn = self._full_fns.get(prompt_len)
        if fn is None:
            fn = build_prefill_only_step(self.ap, self.ctx, self.mesh,
                                         prompt_len=prompt_len,
                                         **self._step_kw).jit()
            self._full_fns[prompt_len] = fn
        return fn

    def prefill(self, req: Request) -> Tuple[int, KVBundle]:
        """Run one request's prompt; return (first token, KV bundle)."""
        S = int(req.prompt.shape[0])
        if S + 1 > self.s_max:
            raise ValueError(f"prompt len {S} + 1 exceeds s_max="
                             f"{self.s_max}")
        t0 = time.perf_counter()
        kv_map = self.ap.gqa.kv_map
        # the request's sampling chain: first token is fold_in(base, 0);
        # the base key rides the bundle so the decode pool continues the
        # exact chain (sampled disagg == colocated, token for token)
        base = request_sampling_key(self.seed, req.rid)
        first = jax.random.fold_in(base, 0)
        if self.admit_mode == "full":
            tok, k, v = self._full_fn(S)(
                self.params, jnp.asarray(req.prompt[None]), first)
            bundle = KVBundle(k=slots_to_heads(np.asarray(k)[:, 0], kv_map),
                              v=slots_to_heads(np.asarray(v)[:, 0], kv_map))
        else:
            tok, self.cache = run_chunked_prefill(
                self.params, self.cache, req.prompt, 0, self.admit_chunk,
                self._chunk_mid, self._chunk_final, self._rng, first)
            row = self._table_row[:] if self.block_size > 0 else None
            bundle = export_slot(self.cache, 0, S, kv_map, table_row=row)
        # seal: the checksum rides the handoff so splice-time verification
        # catches in-flight corruption (admit_prefilled calls verify())
        bundle.rng = np.asarray(base, np.uint32)
        bundle.seal()
        self.prefills += 1
        self.prompt_tokens += S
        self.wall_s += time.perf_counter() - t0
        # the per-layer AR message of this prefill: (1, S, D) for the
        # full-prompt pass, (1, admit_chunk, D) per chunk on the chunked
        # path (pads included — chunks are fixed-size)
        msg_tokens = S if self.admit_mode == "full" else self.admit_chunk
        itemsize = jnp.dtype(self.cfg.dtype).itemsize
        self.analytic_buckets.add(
            autotune.bucket_of(msg_tokens * self.cfg.d_model * itemsize))
        return int(np.asarray(tok)[0]), bundle

    def reset_stats(self) -> None:
        self.prefills = 0
        self.prompt_tokens = 0
        self.wall_s = 0.0
        self.analytic_buckets = set()

    def stats(self) -> Dict[str, Any]:
        return {
            "prefills": self.prefills,
            "prompt_tokens": self.prompt_tokens,
            "wall_s": self.wall_s,
            "mean_prompt_len": self.prompt_tokens / self.prefills
            if self.prefills else 0.0,
            "ar_buckets_analytic": sorted(self.analytic_buckets),
            "ar_buckets_dispatched": self.tuner.lookup_buckets(),
        }


@dataclasses.dataclass
class _Handoff:
    """One prefilled context in the handoff queue, with transfer-retry
    state: ``attempts`` counts failed transfer attempts of *this* bundle
    (the retry cap), ``next_try`` is the backoff horizon (logical steps),
    ``prefill_no`` identifies which prefill of the request produced the
    payload (corruption is a property of the payload, so it is keyed
    here — a corrupt bundle stays corrupt across retries)."""
    req: Request
    tok: int
    bundle: KVBundle
    prefill_no: int
    attempts: int = 0
    next_try: float = 0.0


@dataclasses.dataclass
class DisaggMetrics:
    """Disaggregated trace-replay metrics with per-pool attribution.

    TTFT decomposes into the prefill-pool component (queueing wait +
    prefill tick) and the transfer component (handoff-queue wait until a
    decode slot took the bundle); TPOT is purely the decode pool's
    cadence.  ``*_ar_bucket`` report each pool's all-reduce operating
    point as the max log2 message-size bucket it keyed (observed tuner
    lookups on a mesh with ``ar_strategy="auto"``; the analytic bucket of
    the pool's per-layer message otherwise) — the disaggregation payoff is
    ``prefill_ar_bucket > decode_ar_bucket``: each pool's table serves a
    different regime of the paper's strategy crossover.
    """
    requests: int
    completed: int
    total_new_tokens: int
    steps: int
    wall_s: float
    throughput_tok_s: float
    ttft_steps_p50: float
    ttft_steps_p99: float
    prefill_steps_p50: float     # TTFT component: wait + prefill tick
    transfer_steps_p50: float    # TTFT component: handoff-queue wait
    tpot_steps_p50: float
    tpot_steps_p99: float
    preemptions: int
    handoffs: int
    transfer_bytes: int
    peak_ready_depth: int        # bundles waiting for a decode slot
    peak_pending_depth: int      # prompts waiting for the prefill pool
    prefill_ar_bucket: int
    decode_ar_bucket: int
    prefill_pool: Dict[str, Any]
    decode_pool: Dict[str, Any]
    # robustness (DESIGN.md §11; zeros on a fault-free run):
    # * ``handoff_drops`` / ``handoff_retries`` — transfer attempts lost
    #   to injected drops / retries scheduled with backoff.
    # * ``handoff_corrupt`` — corrupt bundles *detected* (checksum
    #   mismatch at splice time) and routed to re-prefill.
    # * ``handoff_reprefills`` — contexts recomputed from the prompt
    #   after exhausting transfer retries or failing verification.
    # * ``shed_requests`` — never-admitted requests dropped on deadline
    #   expiry or after ``max_reprefills`` (always reported).
    # * ``backpressure_steps`` — ticks the prefill pool was blocked by a
    #   full handoff queue (``ready_cap``) with prompts still pending.
    # * ``prefill_stall_steps`` / ``decode_stall_steps`` — ticks a pool
    #   was frozen by an injected stall.
    handoff_drops: int = 0
    handoff_retries: int = 0
    handoff_corrupt: int = 0
    handoff_reprefills: int = 0
    shed_requests: int = 0
    backpressure_steps: int = 0
    prefill_stall_steps: int = 0
    decode_stall_steps: int = 0
    ready_cap: int = 0
    # Retained per-request latency samples (logical steps) for lossless
    # fleet aggregation — see ``ServeMetrics.merge``.  Excluded from
    # ``to_dict`` so bench JSON rows stay scalar-only.
    ttft_steps_samples: List[float] = dataclasses.field(
        default_factory=list, repr=False)
    prefill_steps_samples: List[float] = dataclasses.field(
        default_factory=list, repr=False)
    transfer_steps_samples: List[float] = dataclasses.field(
        default_factory=list, repr=False)
    tpot_steps_samples: List[float] = dataclasses.field(
        default_factory=list, repr=False)

    SAMPLE_FIELDS = ("ttft_steps_samples", "prefill_steps_samples",
                     "transfer_steps_samples", "tpot_steps_samples")

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        for k in self.SAMPLE_FIELDS:
            d.pop(k, None)
        return d

    @classmethod
    def merge(cls, parts: List["DisaggMetrics"]) -> "DisaggMetrics":
        """Lossless fleet aggregation over per-replica disagg metrics:
        counters/totals summed, percentiles recomputed from retained
        samples, ``steps``/``wall_s`` max (lockstep logical clock), queue
        peaks max (worst replica — per-replica queues peak at different
        ticks, so summing would overstate), AR buckets max, per-pool
        stat dicts dropped (per-replica detail stays with the router)."""
        if not parts:
            raise ValueError("merge() needs at least one DisaggMetrics")
        ttft = [s for m in parts for s in m.ttft_steps_samples]
        pre = [s for m in parts for s in m.prefill_steps_samples]
        xfer = [s for m in parts for s in m.transfer_steps_samples]
        tpot = [s for m in parts for s in m.tpot_steps_samples]
        wall = max(m.wall_s for m in parts)
        total_new = sum(m.total_new_tokens for m in parts)
        return cls(
            requests=sum(m.requests for m in parts),
            completed=sum(m.completed for m in parts),
            total_new_tokens=total_new,
            steps=max(m.steps for m in parts), wall_s=wall,
            throughput_tok_s=total_new / wall if wall > 0 else 0.0,
            ttft_steps_p50=_percentile(ttft, 50),
            ttft_steps_p99=_percentile(ttft, 99),
            prefill_steps_p50=_percentile(pre, 50),
            transfer_steps_p50=_percentile(xfer, 50),
            tpot_steps_p50=_percentile(tpot, 50),
            tpot_steps_p99=_percentile(tpot, 99),
            preemptions=sum(m.preemptions for m in parts),
            handoffs=sum(m.handoffs for m in parts),
            transfer_bytes=sum(m.transfer_bytes for m in parts),
            peak_ready_depth=max(m.peak_ready_depth for m in parts),
            peak_pending_depth=max(m.peak_pending_depth for m in parts),
            prefill_ar_bucket=max(m.prefill_ar_bucket for m in parts),
            decode_ar_bucket=max(m.decode_ar_bucket for m in parts),
            prefill_pool={}, decode_pool={},
            handoff_drops=sum(m.handoff_drops for m in parts),
            handoff_retries=sum(m.handoff_retries for m in parts),
            handoff_corrupt=sum(m.handoff_corrupt for m in parts),
            handoff_reprefills=sum(m.handoff_reprefills for m in parts),
            shed_requests=sum(m.shed_requests for m in parts),
            backpressure_steps=sum(m.backpressure_steps for m in parts),
            prefill_stall_steps=sum(m.prefill_stall_steps for m in parts),
            decode_stall_steps=sum(m.decode_stall_steps for m in parts),
            ready_cap=max(m.ready_cap for m in parts),
            ttft_steps_samples=ttft, prefill_steps_samples=pre,
            transfer_steps_samples=xfer, tpot_steps_samples=tpot)


class DisaggCoordinator:
    """Router between a :class:`PrefillPool` and a decode-side
    :class:`ContinuousBatcher` (see module docstring for the tick model).

    ``decode`` must have been built with ``ar_table=<its own tuner>``
    (see :func:`pool_tuner`) for per-pool dispatch attribution; pass that
    tuner as ``decode_tuner`` so metrics can report its observed buckets.
    """

    def __init__(self, prefill: PrefillPool, decode: ContinuousBatcher, *,
                 prefill_per_step: int = 1,
                 decode_tuner: Optional[autotune.AutoTuner] = None,
                 injector: Optional[FaultInjector] = None,
                 max_handoff_retries: int = 3, retry_backoff: float = 1.0,
                 max_ready: Optional[int] = None, max_reprefills: int = 2,
                 deadline_s: Optional[float] = None):
        """Robustness knobs (DESIGN.md §11, all with fault-free-neutral
        defaults): a transfer attempt lost to an injected drop is retried
        after ``retry_backoff * attempts`` steps, up to
        ``max_handoff_retries`` retries; beyond that (or on a splice-time
        checksum mismatch) the context is *re-prefilled* from the prompt,
        up to ``max_reprefills`` times, after which the request is shed
        with reason ``"handoff_failed"`` — every path terminates.
        ``max_ready`` bounds the handoff queue (default
        ``max(2 * decode.slots, 8)``): a full queue backpressures the
        prefill pool instead of growing without bound.  ``deadline_s``
        is the default TTFT deadline in logical steps (per-request
        deadlines tighten it); expired never-admitted requests are shed
        with reason ``"deadline"``."""
        if prefill.cfg.name != decode.cfg.name:
            raise ValueError(f"pool configs differ: {prefill.cfg.name!r} "
                             f"vs {decode.cfg.name!r}")
        if decode.cfg.family != "dense":
            raise ValueError("disaggregated serving supports dense "
                             f"families only, not {decode.cfg.family!r}")
        if prefill.s_max > decode.s_max:
            # fail fast: a prompt the prefill pool accepts must always
            # fit the decode pool (handoff needs T + 1 <= decode s_max)
            raise ValueError(f"prefill s_max={prefill.s_max} exceeds "
                             f"decode s_max={decode.s_max}; oversized "
                             f"prefills could never hand off")
        if max_handoff_retries < 0 or max_reprefills < 0:
            raise ValueError("retry/re-prefill caps must be >= 0")
        self.prefill = prefill
        self.decode = decode
        self.prefill_per_step = prefill_per_step
        self.decode_tuner = decode_tuner
        self.injector = injector
        self.max_handoff_retries = max_handoff_retries
        self.retry_backoff = retry_backoff
        self.max_ready = max_ready if max_ready is not None \
            else max(2 * decode.slots, 8)
        self.max_reprefills = max_reprefills
        self.deadline_s = deadline_s
        self._records: Dict[int, Dict[str, float]] = {}
        self.transfer_bytes = 0
        self.handoffs = 0
        self.peak_ready = 0
        self.peak_pending = 0
        self._wall = 0.0
        # robustness counters (reset per run)
        self.handoff_drops = 0
        self.handoff_retries = 0
        self.handoff_corrupt = 0
        self.handoff_reprefills = 0
        self.backpressure_steps = 0
        self.prefill_stall_steps = 0
        self.decode_stall_steps = 0
        self._shed: List[Request] = []
        self._reprefills: Dict[int, int] = {}   # rid -> re-prefill count
        # cross-tick queue state (reset by begin_run): an external driver
        # (run(), or inference.router.Router) owns the pending-prompt
        # list and passes it per tick; the handoff queue and the
        # per-request attempt/prefill counters live here
        self._ready: List[_Handoff] = []   # awaiting a decode slot
        self._attempt_no: Dict[int, int] = {}  # rid -> transfer attempts
        self._prefill_no: Dict[int, int] = {}  # rid -> prefills, ever

    def _shed_req(self, req: Request, now: float, reason: str) -> None:
        """Drop a never-admitted request, *reporting* it (shed_reason /
        metrics counter) — shedding is load control, not silent loss."""
        req.shed_step = int(now)
        req.shed_reason = reason
        self._shed.append(req)

    def _deadline(self, req: Request) -> float:
        d = req.deadline_s
        if self.deadline_s is not None:
            d = min(d, self.deadline_s)
        return d

    def _reprefill_or_shed(self, h: _Handoff, pending: List[Request],
                           now: float, reason: str) -> None:
        """Escalation ladder after a handoff gave up (retries exhausted or
        payload corrupt): recompute the context from the prompt (front of
        the prefill queue, preserving age order), bounded by
        ``max_reprefills``; beyond that, shed.  The re-prefill replays the
        request's sampling chain, so a recovered request's tokens are
        bitwise-identical to the fault-free trace."""
        n = self._reprefills.get(h.req.rid, 0)
        if n >= self.max_reprefills:
            self._shed_req(h.req, now, reason)
            return
        self._reprefills[h.req.rid] = n + 1
        self.handoff_reprefills += 1
        pending.insert(0, h.req)

    def begin_run(self) -> None:
        """Reset all per-run state (records, counters, queues, pool
        stats) ahead of a trace replay — called by :meth:`run`, and by an
        external driver (``inference.router.Router``) before it starts
        ticking this coordinator directly."""
        self._records = {}
        self.transfer_bytes = 0
        self.handoffs = 0
        self.peak_ready = 0
        self.peak_pending = 0
        self.handoff_drops = self.handoff_retries = 0
        self.handoff_corrupt = self.handoff_reprefills = 0
        self.backpressure_steps = 0
        self.prefill_stall_steps = self.decode_stall_steps = 0
        self._shed = []
        self._reprefills = {}
        self._ready = []
        self._attempt_no = {}
        self._prefill_no = {}
        self.decode.reset_run_stats()
        self.prefill.reset_stats()

    def _tick_pre(self, pending: List[Request], now: float) -> None:
        """Tick phases ahead of the decode step: deadline sheds, the
        prefill phase (stall-checked even when there is nothing to
        prefill — a stall is a property of the tick, not the queue), and
        the handoff drain into free decode slots.  ``pending`` is the
        externally-owned prompt queue, mutated in place."""
        inj = self.injector
        decode = self.decode
        ready = self._ready
        # deadline shedding: never-admitted requests only (a preempted
        # decode context already emitted its first token — protected)
        for r in [r for r in pending
                  if now - r.arrival_s > self._deadline(r)]:
            self._shed_req(r, now, "deadline")
            pending.remove(r)
        for h in [h for h in ready
                  if now - h.req.arrival_s > self._deadline(h.req)]:
            self._shed_req(h.req, now, "deadline")
            ready.remove(h)
        if inj is not None and inj.prefill_stalled(now):
            self.prefill_stall_steps += 1
        else:
            for _ in range(self.prefill_per_step):
                if not pending:
                    break
                if len(ready) >= self.max_ready:
                    # bounded handoff queue: hold the prompt instead
                    # of growing ready without bound
                    self.backpressure_steps += 1
                    break
                req = pending.pop(0)
                n = self._prefill_no.get(req.rid, 0)
                self._prefill_no[req.rid] = n + 1
                tok, bundle = self.prefill.prefill(req)
                if inj is not None and \
                        inj.corrupt_handoff(req.rid, n):
                    FaultInjector.corrupt_bundle(bundle)
                rec = self._records.setdefault(
                    req.rid, {"arrival": req.arrival_s})
                rec["prefill_step"] = now
                self.handoffs += 1
                self.transfer_bytes += bundle.nbytes
                ready.append(_Handoff(req, tok, bundle, prefill_no=n))
        # handoff queue -> free decode slots, FIFO among *due* entries
        # (retry backoff defers an entry without starving the rest);
        # a bundle that does not fit the paged pool right now stays
        # queued (head-of-line: admitting out of order would starve
        # the oldest context)
        for s in range(decode.slots):
            if decode.active[s] is not None:
                continue
            h = next((h for h in ready if h.next_try <= now), None)
            if h is None:
                continue
            a = self._attempt_no.get(h.req.rid, 0)
            self._attempt_no[h.req.rid] = a + 1
            if inj is not None and inj.drop_handoff(h.req.rid, a):
                # transfer attempt lost in flight
                self.handoff_drops += 1
                h.attempts += 1
                if h.attempts > self.max_handoff_retries:
                    ready.remove(h)
                    self._reprefill_or_shed(h, pending, now,
                                            "handoff_failed")
                else:
                    self.handoff_retries += 1
                    h.next_try = now + self.retry_backoff * h.attempts
                continue
            try:
                ok = decode.admit_prefilled(s, h.req, h.bundle,
                                            h.tok, now)
            except BundleIntegrityError:
                # splice-time checksum mismatch: the payload itself is
                # bad — retrying the same bundle can never succeed
                self.handoff_corrupt += 1
                ready.remove(h)
                self._reprefill_or_shed(h, pending, now,
                                        "handoff_corrupt")
                continue
            if ok:
                ready.remove(h)
                self._records[h.req.rid]["handoff_step"] = now
        self.peak_ready = max(self.peak_ready, len(ready))
        self.peak_pending = max(self.peak_pending, len(pending))

    def _tick_decode(self, pending: List[Request], now: float) -> None:
        """Decode phase of one tick: one decode-pool step (unless
        stalled), then reroute decode-pool preemptions back to the front
        of the prompt queue for recompute."""
        inj = self.injector
        decode = self.decode
        if inj is not None and inj.decode_stalled(now):
            self.decode_stall_steps += 1
        else:
            decode.step(now)
        # a preempted decode context lost its KV: route it back to the
        # prefill pool for recompute (front of queue, preserving the
        # eviction order — the colocated batcher's requeue-first rule)
        if decode._requeue:
            pending[:0] = decode._requeue
            decode._requeue.clear()

    def tick(self, arrived: List[Request], now: float) -> None:
        """One full logical tick on an externally-owned prompt queue —
        the ``ContinuousBatcher.tick`` contract, so a router drives a
        colocated batcher and a disagg coordinator identically.  (The
        trailing drained tick is harmless: both phases no-op on empty
        queues, matching the batcher's no-op ``step``.)"""
        self._tick_pre(arrived, now)
        self._tick_decode(arrived, now)

    def drained(self, arrived: List[Request]) -> bool:
        """No queued, in-flight, or active work left for this replica."""
        return not arrived and not self._ready \
            and all(a is None for a in self.decode.active)

    def run(self, requests: List[Request],
            max_steps: int = 100000) -> List[Request]:
        """Replay a trace (same contract as ``ContinuousBatcher.run``).

        Per tick: arrivals queue for prefill; deadline-expired
        never-admitted requests are shed; the prefill pool (unless stalled
        or backpressured by a full handoff queue) prefills up to
        ``prefill_per_step`` prompts; free decode slots admit the oldest
        *due* handoff (entries inside their retry-backoff window are
        skipped, capacity rejects keep head-of-line order); the decode
        pool (unless stalled) runs one step.  Failed or corrupt handoffs
        walk the retry → re-prefill → shed ladder (bounded at every rung,
        so ``run`` terminates at any fault rate)."""
        waiting = sorted(requests, key=lambda r: r.arrival_s)
        qi = 0
        now = 0.0
        pending: List[Request] = []   # awaiting prefill
        self.begin_run()
        wall0 = time.perf_counter()
        for _ in range(max_steps):
            while qi < len(waiting) and waiting[qi].arrival_s <= now:
                pending.append(waiting[qi])
                qi += 1
            self._tick_pre(pending, now)
            if qi >= len(waiting) and self.drained(pending):
                break
            self._tick_decode(pending, now)
            now += 1.0
        self._wall = time.perf_counter() - wall0
        decode = self.decode
        decode._wall_run = self._wall
        return requests

    # -- metrics -------------------------------------------------------------

    def _decode_bucket(self) -> int:
        """Decode pool's AR operating point: observed tuner lookups when
        available, else the analytic per-layer message bucket (all slots
        x 1 token x d_model; x (k+1) under speculative verify)."""
        if self.decode_tuner is not None:
            seen = self.decode_tuner.lookup_buckets()
            if seen:
                return max(seen)
        cfg = self.decode.cfg
        tokens = self.decode.slots
        if self.decode.spec_mode:
            tokens *= self.decode.spec_k + 1
        itemsize = jnp.dtype(cfg.dtype).itemsize
        return autotune.bucket_of(tokens * cfg.d_model * itemsize)

    def _prefill_bucket(self) -> int:
        seen = self.prefill.tuner.lookup_buckets()
        if seen:
            return max(seen)
        return max(self.prefill.analytic_buckets, default=0)

    def metrics(self, requests: List[Request]) -> DisaggMetrics:
        dm = self.decode.metrics(requests)   # TPOT / cache / spec fields
        done = [r for r in requests if r.output is not None]
        pre, xfer, ttft = [], [], []
        for r in done:
            rec = self._records.get(r.rid)
            if rec is None or "handoff_step" not in rec:
                continue
            p = max(rec["prefill_step"] - rec["arrival"], 0.0) + 1.0
            t = rec["handoff_step"] - rec["prefill_step"]
            pre.append(p)
            xfer.append(t)
            ttft.append(p + t)
        return DisaggMetrics(
            requests=len(requests), completed=len(done),
            total_new_tokens=dm.total_new_tokens, steps=dm.steps,
            wall_s=self._wall,
            throughput_tok_s=dm.total_new_tokens / self._wall
            if self._wall > 0 else 0.0,
            ttft_steps_p50=_percentile(ttft, 50),
            ttft_steps_p99=_percentile(ttft, 99),
            prefill_steps_p50=_percentile(pre, 50),
            transfer_steps_p50=_percentile(xfer, 50),
            tpot_steps_p50=dm.tpot_steps_p50,
            tpot_steps_p99=dm.tpot_steps_p99,
            preemptions=dm.preemptions,
            handoffs=self.handoffs,
            transfer_bytes=self.transfer_bytes,
            peak_ready_depth=self.peak_ready,
            peak_pending_depth=self.peak_pending,
            prefill_ar_bucket=self._prefill_bucket(),
            decode_ar_bucket=self._decode_bucket(),
            prefill_pool=self.prefill.stats(),
            decode_pool=dm.to_dict(),
            handoff_drops=self.handoff_drops,
            handoff_retries=self.handoff_retries,
            handoff_corrupt=self.handoff_corrupt,
            handoff_reprefills=self.handoff_reprefills,
            shed_requests=len(self._shed),
            backpressure_steps=self.backpressure_steps,
            prefill_stall_steps=self.prefill_stall_steps,
            decode_stall_steps=self.decode_stall_steps,
            ready_cap=self.max_ready,
            ttft_steps_samples=ttft, prefill_steps_samples=pre,
            transfer_steps_samples=xfer,
            tpot_steps_samples=list(dm.tpot_steps_samples))


__all__ = ["PrefillPool", "DisaggCoordinator", "DisaggMetrics",
           "pool_tuner"]
