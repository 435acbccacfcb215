"""Serving driver: batched generation, trace-replay continuous batching,
disaggregated prefill/decode pools, or a multi-replica routed fleet (see
docs/serving.md for the full flag reference).

    python -m repro.launch.serve --arch llama3.2-1b --smoke --mode batch
    python -m repro.launch.serve --arch llama3.2-1b --smoke --mode trace \
        --block-size 8 --ar-strategy auto --overlap
    python -m repro.launch.serve --arch llama3.2-1b --mode trace --tp 8 \
        --pods 2 --block-size 8   # under XLA_FLAGS=...device_count=8
    python -m repro.launch.serve --arch llama3.2-1b --mode trace \
        --spec-mode ngram --spec-k 4   # speculative decoding (DESIGN.md §8)
    python -m repro.launch.serve --arch llama3.2-1b --mode trace --disagg \
        --prefill-tp 8 --prefill-pods 2 --decode-tp 4 --block-size 8
        # disaggregated pools (DESIGN.md §9); per-pool mesh + ar_table
    python -m repro.launch.serve --arch llama3.2-1b --mode trace \
        --replicas 2 --tp 4 --router-policy ttft_aware
        # multi-replica router (DESIGN.md §13); disjoint mesh per replica

Every deployment is described by a :class:`~repro.inference.ServeSpec`
(``ServeSpec.from_args``): one validated, JSON-round-trippable value
that the factories (``build_engine`` / ``build_replica``) and the router
construct from — the CLI, tests, and benchmarks share one construction
path and reject invalid combos identically.

Trace mode replays a BurstGPT-style synthetic trace through the
continuous batcher (local path, or the mesh path when --tp > 1) and
reports:

  TTFT   time-to-first-token: queueing wait + prefill, per request
  TPOT   time-per-output-token: decode cadence once generation started

both as p50/p99 in logical engine steps (deterministic) and in wall
seconds (steps x measured mean step time), plus cache utilization and
preemption counts from the paged KV allocator.  With ``--disagg`` the
TTFT is attributed to the prefill pool + handoff transfer, TPOT to the
decode pool, and each pool reports its own all-reduce message-size
buckets.  With ``--replicas N`` the trace is load-balanced over N
self-contained replicas and the report adds placement counts, load
imbalance, and the lossless fleet metric merge.
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import numpy as np

from ..configs import get_config, get_smoke, ARCH_IDS
from ..core.pcontext import AR_STRATEGIES, SEQ_PARALLEL_MODES
from ..inference.router import Router
from ..inference.scheduler import make_prefix_trace, make_trace
from .compile_cache import enable_compile_cache
from ..inference.spec import (PREFIX_MODES, ROUTER_POLICIES, ServeSpec,
                              SpecError, build_engine, build_replica)


def _cfg(spec: ServeSpec):
    r = spec.replica
    return get_smoke(r.arch) if r.smoke else get_config(r.arch)


def _check_outcomes(done, injector, deadline_ms):
    """The never-silently-dropped contract: on a fault-free run with no
    deadline every request must complete; under faults/deadlines each
    request either completed or was shed *with a reason*."""
    if injector is None and deadline_ms is None:
        assert all(r.output is not None for r in done), "requests dropped!"
    else:
        lost = [r.rid for r in done
                if r.output is None and r.shed_reason is None]
        assert not lost, f"requests silently dropped: {lost}"


def _print_faults(m, injector, shed):
    """One summary line for the robustness counters (trace modes)."""
    if injector is not None:
        fired = {k: v for k, v in injector.stats().items() if v}
        print(f"[serve]   faults injected: {fired or 'none'}")
    if shed:
        reasons: dict = {}
        for r in shed:
            reasons[r.shed_reason] = reasons.get(r.shed_reason, 0) + 1
        print(f"[serve]   shed {len(shed)} request(s): {reasons}")


def _write_json(m, json_out):
    if json_out:
        with open(json_out, "w") as f:
            json.dump(m.to_dict(), f, indent=2, default=float)
        print(f"[serve]   metrics -> {json_out}")


def _make_reqs(spec: ServeSpec, *, n_requests, mean_in, mean_out, rate,
               shared_frac: float = 0.0, prefix_len: int = 32):
    """The trace every trace-mode deployment replays: plain BurstGPT-style
    (:func:`make_trace`), or the shared-system-prompt variant
    (:func:`make_prefix_trace`) when ``--shared-frac`` > 0 — the same
    trace either way for a given seed, so prefix-cache on/off runs are
    comparable token-for-token."""
    r = spec.replica
    cfg = _cfg(spec)
    if shared_frac > 0.0:
        return make_prefix_trace(
            n_requests, prefix_len=prefix_len, shared_frac=shared_frac,
            mean_in=mean_in, mean_out=mean_out, rate=rate,
            vocab=cfg.vocab_size, seed=r.seed, clip_len=r.s_max - 1)
    return make_trace(n_requests, mean_in=mean_in, mean_out=mean_out,
                      rate=rate, vocab=cfg.vocab_size, seed=r.seed)


def run_batch(spec: ServeSpec, *, batch: int = 4, prompt_len: int = 16,
              max_new: int = 16):
    """Batched generation through :func:`build_engine` (DESIGN.md §13:
    the spec is the only construction path)."""
    r = spec.replica
    cfg = _cfg(spec)
    s_max = prompt_len + max_new + 8
    if r.block_size:
        s_max = -(-s_max // r.block_size) * r.block_size
    eng = build_engine(r.replace(s_max=s_max))
    rng = np.random.default_rng(r.seed)
    prompts = rng.integers(0, cfg.vocab_size, (batch, prompt_len))
    extra = {}
    if cfg.family == "encdec":
        extra["frame_embeds"] = jax.numpy.asarray(
            rng.standard_normal((batch, cfg.enc_seq, cfg.d_model)),
            cfg.dtype)
    if cfg.family == "vlm":
        extra["patch_embeds"] = jax.numpy.asarray(
            rng.standard_normal((batch, cfg.n_patches, cfg.d_model)),
            cfg.dtype)
    res = eng.generate(prompts, max_new, extra=extra)
    layout = f"paged(bs={r.block_size})" if r.block_size else "dense"
    sp = f" spec={r.spec_mode}(k={r.spec_k})" if r.spec_mode else ""
    print(f"[serve] {r.arch}: batch {batch} prompt {prompt_len} "
          f"new {max_new} ar={r.ar_strategy} tp={r.tp} {layout}{sp} "
          f"| prefill {res.prefill_s*1e3:.0f}ms "
          f"decode {res.decode_s*1e3:.0f}ms "
          f"({res.decode_tokens_per_s:.0f} tok/s, {res.steps} steps)")
    return res


def _print_trace_metrics(spec: ServeSpec, m, slots: int):
    r = spec.replica
    ar = r.ar_strategy
    if r.ar_quant != "none":
        ar = f"{ar}/q={r.ar_quant}"
    layout = f"paged(bs={r.block_size})" if r.block_size else "dense"
    if r.kv_quant:
        layout += "+kv8"
    if r.prefix_cache == "on":
        layout += "+prefix"
    print(f"[serve] trace {r.arch} [{layout} ar={ar} tp={r.tp}"
          f"{' overlap' if r.overlap else ''}]: "
          f"{m.completed}/{m.requests} reqs, {m.total_new_tokens} tokens "
          f"in {m.wall_s:.1f}s ({m.throughput_tok_s:.0f} tok/s, "
          f"slots={slots}, {m.steps} steps)")
    print(f"[serve]   TTFT p50/p99: {m.ttft_steps_p50:.1f}/"
          f"{m.ttft_steps_p99:.1f} steps | TPOT p50/p99: "
          f"{m.tpot_steps_p50:.2f}/{m.tpot_steps_p99:.2f} steps")
    print(f"[serve]   KV peak {m.peak_kv_tokens} tokens of "
          f"{m.kv_capacity_tokens} reserved "
          f"(util {m.cache_utilization:.2f}), "
          f"{m.preemptions} preemptions")
    if r.prefix_cache == "on":
        print(f"[serve]   prefix cache: {m.prefix_hits}/"
              f"{m.prefix_lookups} admissions hit "
              f"(rate {m.prefix_hit_rate:.2f}), "
              f"{m.prefix_tokens_saved} prompt tokens spliced "
              "instead of re-prefilled")
    if r.spec_mode:
        print(f"[serve]   spec[{r.spec_mode} k_mean={m.spec_k_mean:.1f}"
              f"{' adaptive' if r.spec_adaptive else ''}]: "
              f"{m.accepted_tokens}/{m.drafted_tokens} drafts accepted "
              f"(rate {m.acceptance_rate:.2f}), "
              f"{m.accepted_tokens_per_step:.2f} accepted/step over "
              f"{m.spec_steps} verify steps, drafter hit rate "
              f"{m.drafter_hit_rate:.2f}")


def run_trace(spec: ServeSpec, *, n_requests: int = 12, mean_in: int = 12,
              mean_out: int = 10, rate: float = 2.0, json_out=None,
              shared_frac: float = 0.0, prefix_len: int = 32):
    """Colocated trace replay: one :func:`build_replica` batcher."""
    r = spec.replica
    sched = build_replica(r)
    injector = sched.injector
    reqs = _make_reqs(spec, n_requests=n_requests, mean_in=mean_in,
                      mean_out=mean_out, rate=rate,
                      shared_frac=shared_frac, prefix_len=prefix_len)
    done = sched.run(reqs)
    _check_outcomes(done, injector, r.deadline_ms)
    m = sched.metrics(done)
    _print_trace_metrics(spec, m, r.slots)
    if injector is not None or m.shed_requests:
        print(f"[serve]   robustness: {m.quarantines} quarantines, "
              f"{m.injected_oom} injected OOM, {m.straggler_steps} "
              f"straggler steps, {m.spec_autodisables} spec autodisables")
        _print_faults(m, injector, sched._shed)
    _write_json(m, json_out)
    return done, m


def run_disagg(spec: ServeSpec, *, n_requests: int = 12, mean_in: int = 12,
               mean_out: int = 10, rate: float = 2.0, json_out=None,
               shared_frac: float = 0.0, prefix_len: int = 32):
    """Disaggregated trace serving: prefill pool + decode pool, each with
    its own mesh layout and AR dispatch table (DESIGN.md §9), built from
    one :func:`build_replica` call.  ``spec.replica.ar_table`` seeds BOTH
    pools when a per-pool table is not given; ``fault_plan`` /
    ``deadline_ms`` arm the robustness layer (DESIGN.md §11)."""
    r = spec.replica
    coord = build_replica(r)
    decode, injector = coord.decode, coord.injector
    reqs = _make_reqs(spec, n_requests=n_requests, mean_in=mean_in,
                      mean_out=mean_out, rate=rate,
                      shared_frac=shared_frac, prefix_len=prefix_len)
    done = coord.run(reqs)
    _check_outcomes(done, injector, r.deadline_ms)
    m = coord.metrics(done)
    layout = f"paged(bs={r.block_size})" if decode.paged else "dense"
    sp = f" spec={r.spec_mode}(k={r.spec_k})" if r.spec_mode else ""
    print(f"[serve] disagg {r.arch} [{layout} ar={r.ar_strategy} "
          f"prefill tp={r.prefill_tp}x{r.prefill_pods} decode "
          f"tp={r.decode_tp}x{r.decode_pods}{sp}]: "
          f"{m.completed}/{m.requests} reqs, "
          f"{m.total_new_tokens} tokens in {m.wall_s:.1f}s "
          f"({m.throughput_tok_s:.0f} tok/s, {m.steps} decode steps)")
    print(f"[serve]   TTFT p50/p99: {m.ttft_steps_p50:.1f}/"
          f"{m.ttft_steps_p99:.1f} steps "
          f"(prefill {m.prefill_steps_p50:.1f} + transfer "
          f"{m.transfer_steps_p50:.1f} at p50) | TPOT p50/p99: "
          f"{m.tpot_steps_p50:.2f}/{m.tpot_steps_p99:.2f} steps "
          f"[decode pool]")
    print(f"[serve]   handoff: {m.handoffs} bundles, "
          f"{m.transfer_bytes / 1024:.0f} KiB, ready/pending queue peaks "
          f"{m.peak_ready_depth}/{m.peak_pending_depth}, "
          f"{m.preemptions} decode-pool preemptions")
    print(f"[serve]   AR buckets: prefill pool 2^{m.prefill_ar_bucket} "
          f"vs decode pool 2^{m.decode_ar_bucket} "
          f"(prefill {m.prefill_pool['ar_buckets_analytic']} analytic, "
          f"{m.prefill_pool['ar_buckets_dispatched']} dispatched)")
    if injector is not None or m.shed_requests:
        print(f"[serve]   robustness: {m.handoff_drops} drops / "
              f"{m.handoff_retries} retries / {m.handoff_corrupt} corrupt "
              f"/ {m.handoff_reprefills} re-prefills, "
              f"{m.backpressure_steps} backpressure steps "
              f"(ready cap {m.ready_cap}), stalls prefill="
              f"{m.prefill_stall_steps} decode={m.decode_stall_steps}")
        _print_faults(m, injector, coord._shed + decode._shed)
    _write_json(m, json_out)
    return done, m


def run_router(spec: ServeSpec, *, n_requests: int = 12, mean_in: int = 12,
               mean_out: int = 10, rate: float = 2.0, json_out=None,
               shared_frac: float = 0.0, prefix_len: int = 32):
    """Multi-replica trace serving (DESIGN.md §13): ``spec.replicas``
    self-contained replicas on disjoint device groups, placed by
    ``spec.router_policy``, reported as per-replica metrics plus their
    lossless fleet merge."""
    r = spec.replica
    router = Router.from_spec(spec)
    reqs = _make_reqs(spec, n_requests=n_requests, mean_in=mean_in,
                      mean_out=mean_out, rate=rate,
                      shared_frac=shared_frac, prefix_len=prefix_len)
    done = router.run(reqs)
    # each replica has an independently-seeded injector; outcome checking
    # only needs to know whether ANY faults/deadlines were armed
    injector = router.replicas[0].injector
    _check_outcomes(done, injector, r.deadline_ms)
    rm = router.metrics(done)
    m = rm.fleet
    kind = "disagg" if r.disagg else \
        (f"tp={r.tp}" if r.tp > 1 else "local")
    print(f"[serve] router {r.arch} [{spec.replicas}x {kind} "
          f"policy={spec.router_policy}]: {m.completed}/{m.requests} reqs, "
          f"{m.total_new_tokens} tokens in {m.wall_s:.1f}s "
          f"({m.throughput_tok_s:.0f} tok/s, {m.steps} steps)")
    print(f"[serve]   fleet TTFT p50/p99: {m.ttft_steps_p50:.1f}/"
          f"{m.ttft_steps_p99:.1f} steps | TPOT p50/p99: "
          f"{m.tpot_steps_p50:.2f}/{m.tpot_steps_p99:.2f} steps")
    print(f"[serve]   placements {rm.placements} "
          f"(imbalance {rm.load_imbalance:.2f}), preemptions "
          f"{m.preemptions}, shed {m.shed_requests}")
    if r.prefix_cache == "on":
        # per-replica tries (no cross-replica sharing): the fleet line is
        # the lossless sum over replicas
        print(f"[serve]   fleet prefix cache: {m.prefix_hits}/"
              f"{m.prefix_lookups} admissions hit "
              f"(rate {m.prefix_hit_rate:.2f}), "
              f"{m.prefix_tokens_saved} prompt tokens spliced")
    for i, pm in enumerate(rm.per_replica):
        print(f"[serve]   replica {i}: {pm.completed}/{pm.requests} reqs, "
              f"TTFT p99 {pm.ttft_steps_p99:.1f}, "
              f"{pm.total_new_tokens} tokens")
    if injector is not None:
        for i, rep in enumerate(router.replicas):
            fired = {k: v for k, v in rep.injector.stats().items() if v}
            print(f"[serve]   replica {i} faults: {fired or 'none'}")
    _write_json(rm, json_out)
    return done, rm


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI (introspected by tools/check_docs.py: every flag
    added here must be documented in docs/serving.md)."""
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=list(ARCH_IDS), required=True)
    p.add_argument("--mode", choices=["batch", "trace"], default="batch")
    p.add_argument("--smoke", action="store_true", default=True)
    p.add_argument("--full", dest="smoke", action="store_false")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--requests", type=int, default=12)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--s-max", type=int, default=128)
    p.add_argument("--block-size", type=int, default=0,
                   help="paged KV block size (0 = dense layout)")
    p.add_argument("--n-blocks", type=int, default=None,
                   help="physical block pool size (default: full capacity)")
    p.add_argument("--ar-strategy", choices=list(AR_STRATEGIES),
                   default="flat")
    p.add_argument("--ar-table", default=None,
                   help="persisted autotune table for --ar-strategy auto")
    p.add_argument("--overlap", action="store_true",
                   help="overlapped collective-matmul decode path")
    p.add_argument("--seq-parallel", choices=list(SEQ_PARALLEL_MODES),
                   default="off",
                   help="sequence-parallel prefill residual layout: "
                        "reduce-scatter + all-gather replace the fused "
                        "per-residual all-reduce (auto = per-call-site "
                        "message-size dispatch; decode is never "
                        "decomposed)")
    p.add_argument("--ar-quant", choices=["off", "int8", "int4", "auto"],
                   default="off",
                   help="quantized all-reduce wire format: int8/int4 "
                        "payloads with per-group scales and error "
                        "feedback on the decode residuals (auto = "
                        "per-call-site pick among off/int8/int4, "
                        "requires --ar-strategy auto)")
    p.add_argument("--kv-quant", action="store_true",
                   help="int8 KV cache with per-(pos, head) scales "
                        "(trace mode, dense layout, full admission, "
                        "no speculation)")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel ways (mesh path when > 1)")
    p.add_argument("--pods", type=int, default=1,
                   help="split --tp across this many pods (slow axis)")
    p.add_argument("--admit-mode", choices=["full", "chunked"],
                   default="full")
    p.add_argument("--admit-chunk", type=int, default=32)
    p.add_argument("--rate", type=float, default=2.0)
    # -- prefix-sharing radix KV cache (trace mode only) -----------------
    p.add_argument("--prefix-cache", choices=list(PREFIX_MODES),
                   default="off",
                   help="radix prefix cache over paged KV blocks "
                        "(DESIGN.md §14): admission splices the longest "
                        "previously-prefilled prompt prefix via "
                        "copy-on-write block sharing and prefills only "
                        "the suffix (needs --block-size > 0; rejects "
                        "--kv-quant and --disagg)")
    p.add_argument("--prefix-capacity", type=int, default=None,
                   help="max trie-pinned blocks before LRU eviction of "
                        "unreferenced prefix nodes (default: bounded by "
                        "the physical pool)")
    p.add_argument("--shared-frac", type=float, default=0.0,
                   help="fraction of trace requests opening with one "
                        "common system prompt (make_prefix_trace; 0 = "
                        "plain make_trace)")
    p.add_argument("--prefix-len", type=int, default=32,
                   help="length of the shared system prompt for "
                        "--shared-frac > 0")
    p.add_argument("--spec-mode", choices=["none", "ngram", "draft"],
                   default="none",
                   help="speculative decoding drafter (none = off)")
    p.add_argument("--spec-k", type=int, default=4,
                   help="draft tokens verified per fused pass")
    p.add_argument("--spec-adaptive", action="store_true",
                   help="acceptance-rate-adaptive speculation length")
    p.add_argument("--draft-arch", default="llama3.2-1b",
                   help="registry arch for --spec-mode draft")
    p.add_argument("--json", "--metrics-json", dest="json_out",
                   default=None, help="write trace metrics JSON here")
    # -- multi-replica router (trace mode only) --------------------------
    p.add_argument("--replicas", type=int, default=1,
                   help="data-parallel replica count; > 1 serves the "
                        "trace through the router tier, each replica on "
                        "its own disjoint device group (DESIGN.md §13)")
    p.add_argument("--router-policy", choices=list(ROUTER_POLICIES),
                   default="round_robin",
                   help="placement policy for --replicas > 1: "
                        "round_robin (arrival index mod N), least_queue "
                        "(fewest in flight), ttft_aware (smallest "
                        "estimated wait from queue depth + analytic "
                        "prefill cost)")
    # -- disaggregated prefill/decode pools (trace mode only) ------------
    p.add_argument("--disagg", action="store_true",
                   help="disaggregated serving: prefill pool + decode "
                        "pool with per-pool mesh layouts and AR tables")
    p.add_argument("--prefill-tp", type=int, default=1,
                   help="prefill-pool tensor-parallel ways (--disagg)")
    p.add_argument("--prefill-pods", type=int, default=1,
                   help="prefill-pool pod split of --prefill-tp")
    p.add_argument("--decode-tp", type=int, default=1,
                   help="decode-pool tensor-parallel ways (--disagg)")
    p.add_argument("--decode-pods", type=int, default=1,
                   help="decode-pool pod split of --decode-tp")
    p.add_argument("--prefill-ar-table", default=None,
                   help="persisted autotune table for the prefill pool")
    p.add_argument("--decode-ar-table", default=None,
                   help="persisted autotune table for the decode pool")
    p.add_argument("--prefill-per-step", type=int, default=1,
                   help="prompts the prefill pool admits per logical step")
    # -- robustness / fault injection (trace modes) ----------------------
    p.add_argument("--fault-plan", default=None,
                   help="deterministic fault plan: 'key=rate,...' string "
                        "or JSON file (see docs/robustness.md); e.g. "
                        "'seed=7,handoff_drop=0.1,nan_logits=0.02'")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="TTFT deadline; 1 logical step = 1 ms, so this "
                        "is a deterministic step budget — expired "
                        "never-admitted requests are shed (reported, "
                        "never silent)")
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    try:
        spec = ServeSpec.from_args(args)
    except SpecError as e:
        # one validation home (ServeSpec.validate); the CLI only converts
        # the rejection into an exit status
        raise SystemExit(str(e))
    # every CLI combination must survive the JSON round trip (the bench /
    # router serialization contract; cheap, so asserted on every run)
    assert ServeSpec.from_json(spec.to_json()) == spec, "spec round trip"
    if spec.mode == "batch":
        run_batch(spec, batch=args.batch, prompt_len=args.prompt_len,
                  max_new=args.max_new)
        return 0
    if _cfg(spec).family in ("encdec", "vlm"):
        raise SystemExit("trace mode supports text-only archs")
    kw = dict(n_requests=args.requests, rate=args.rate,
              json_out=args.json_out, shared_frac=args.shared_frac,
              prefix_len=args.prefix_len)
    if spec.replicas > 1:
        run_router(spec, **kw)
    elif spec.replica.disagg:
        run_disagg(spec, **kw)
    else:
        run_trace(spec, **kw)
    return 0


if __name__ == "__main__":
    sys.exit(main())
