"""Message-size-aware all-reduce strategy autotuner.

The paper's Sec. 4.3/5 finding is that the best all-reduce algorithm is a
function of message size and topology: recursive doubling (NVRAR) wins in the
latency-bound 128 KB-2 MB regime, ring-style algorithms win once the transfer
is bandwidth-bound.  A single statically chosen ``ParallelCtx.ar_strategy``
therefore leaves performance on the table whenever one program contains
all-reduces on both sides of the crossover (decode: B x H activations; embed:
vocab partials; training: gradient buckets).

This module provides the dispatcher behind ``ar_strategy="auto"``:

* a **dispatch table** keyed on (message-byte bucket, fast-axis size,
  slow-axis size, dtype) mapping to an :class:`ARChoice`
  (strategy + rd_chunks + wire quantization);
* **analytic seeding** from the alpha-beta models in
  :mod:`repro.core.comm_model` (each strategy's predicted time on the
  configured :class:`NetworkSpec`, honest full-exchange form for RD since
  that is what :func:`repro.core.hierarchical.rd_all_reduce` implements);
* **measurement refinement**: callers record observed latencies with
  :meth:`AutoTuner.record`; :meth:`AutoTuner.refine` overrides the analytic
  pick wherever a measured winner exists;
* **JSON persistence** (:meth:`AutoTuner.save` / :meth:`AutoTuner.load`) so a
  tuned table survives across runs and can be shipped with a deployment.

Resolution happens at *trace time* inside ``tp_all_reduce`` — message sizes
are static under jit/shard_map, so "auto" costs nothing at runtime: each call
site is lowered with its own concrete strategy.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import threading
import warnings
from typing import Dict, List, Optional, Tuple, Union

from . import comm_model as cm

# Strategies the dispatcher may pick from (ParallelCtx.ar_strategy values).
DISPATCHABLE = ("flat", "hier_ring", "hier_rd", "hier_rd_halving")

# Wire-quantization levels a table entry may carry (ParallelCtx.ar_quant
# values minus "auto"; kept literal to avoid an import knot with pcontext).
QUANT_LEVELS = ("none", "int8", "int4")

# Persisted-table schema version (``to_json``); bump on incompatible
# layout changes.  ``load`` treats an unknown version as a corrupt table
# and degrades to analytic seeding rather than guessing.
TABLE_VERSION = 1

# Chunked slow-axis exchange kicks in once the per-step inter payload crosses
# this size (paper Sec. 4.2.1: overlap chunk q's reduce with chunk q+1's
# transfer); capped so per-chunk DMA issue latency stays amortized.
_CHUNK_THRESHOLD_BYTES = 256 * 1024
_MAX_RD_CHUNKS = 8


@dataclasses.dataclass(frozen=True)
class ARChoice:
    """One dispatch-table entry: a fully resolved all-reduce configuration."""

    strategy: str                 # one of DISPATCHABLE
    rd_chunks: int = 1            # slow-axis pipeline chunks (hier_rd only)
    quant: str = "none"           # wire quantization level (QUANT_LEVELS)

    def apply(self, ctx):
        """Concretize a ctx whose ar_strategy is 'auto' with this choice.

        ``quant`` is written back only when the ctx asked for
        ``ar_quant="auto"`` — a forced level (or "none") is the caller's
        decision and must survive dispatch.  Both fields go through one
        ``replace`` so the ctx validator never sees the half-resolved
        state (ar_quant='auto' with a concrete strategy)."""
        kw = dict(ar_strategy=self.strategy, rd_chunks=self.rd_chunks)
        if getattr(ctx, "ar_quant", "none") == "auto":
            kw["ar_quant"] = self.quant
        return ctx.replace(**kw)


# ---------------------------------------------------------------------------
# Analytic model: predicted time per strategy
# ---------------------------------------------------------------------------


def predict_times(msg_bytes: float, fast_size: int, slow_size: int,
                  net: cm.NetworkSpec) -> Dict[str, float]:
    """Predicted all-reduce seconds per strategy on ``net``.

    Maps our TP topology onto the paper's (N nodes x G GPUs/node) frame:
    G = fast-axis (ICI) size, N = slow-axis (DCN) size.  ``flat`` is the
    XLA-default single-level ring whose inter-node links dominate (Eq. 1);
    the hierarchical strategies share RS/AG intra phases (Eqs. 3/5) and
    differ in the inter phase: ring, full-exchange recursive doubling
    (Algorithm 1 — what ``rd_all_reduce`` implements), or recursive
    halving/doubling.
    """
    g, n = max(1, fast_size), max(1, slow_size)
    if n <= 1:
        # Single-level group: every strategy degenerates to RS+AG over the
        # fast axis; only 'flat' vs hierarchy-with-one-level remain, and
        # they lower to the same collectives.  Report the intra ring time.
        t = 2.0 * cm.t_reduce_scatter_intra(msg_bytes, g, net)
        return {s: t for s in DISPATCHABLE}
    intra = (cm.t_reduce_scatter_intra(msg_bytes, g, net)
             + cm.t_allgather_intra(msg_bytes, g, net))
    shard = msg_bytes / g  # slow phase operates on the RS-scattered shard
    # inter-node ring all-reduce of the shard over n endpoints
    ring_inter = 2.0 * (n - 1) * net.alpha_inter \
        + 2.0 * (n - 1) / n * (shard / net.beta_inter)
    rd_inter = cm.t_rd_inter_full_exchange(msg_bytes, n, g, net)
    halving_inter = cm.t_rd_halving_inter(msg_bytes, n, g, net)
    return {
        "flat": cm.t_ring_allreduce(msg_bytes, n, g, net),
        "hier_ring": intra + ring_inter,
        "hier_rd": intra + rd_inter,
        "hier_rd_halving": intra + halving_inter,
    }


def _rd_chunks_for(msg_bytes: float, fast_size: int) -> int:
    """Pipeline chunk count for the hier_rd slow exchange (Sec. 4.2.1):
    one chunk per _CHUNK_THRESHOLD_BYTES of the RS-scattered shard,
    capped so per-chunk issue latency stays amortized."""
    shard = msg_bytes / max(1, fast_size)
    return int(min(_MAX_RD_CHUNKS,
                   max(1, shard // _CHUNK_THRESHOLD_BYTES)))


def predict_sp_times(msg_bytes: float, fast_size: int, slow_size: int,
                     net: cm.NetworkSpec) -> Dict[str, float]:
    """Fused-AR vs RS+AG (sequence-parallel) predicted seconds.

    ``fused`` is the best dispatchable all-reduce strategy at this size
    (what ``ar_strategy="auto"`` would run for the residual); ``rs_ag`` is
    the Megatron-SP decomposition — reduce-scatter ending the row-parallel
    projection, all-gather deferred to the next column-parallel input —
    modelled by :func:`repro.core.comm_model.t_sp_rs_ag`.
    """
    fused = min(predict_times(msg_bytes, fast_size, slow_size, net)
                .values())
    return {"fused": fused,
            "rs_ag": cm.t_sp_rs_ag(msg_bytes, max(1, slow_size),
                                   max(1, fast_size), net)}


def analytic_sp_choice(msg_bytes: float, fast_size: int, slow_size: int,
                       net: cm.NetworkSpec) -> bool:
    """True when the RS+AG decomposition beats the best fused all-reduce
    under the alpha-beta model — large (bandwidth-bound) prefill messages;
    False in the latency-bound one-token decode regime, where the extra
    collective launch is pure overhead."""
    if fast_size <= 1:
        return False
    t = predict_sp_times(msg_bytes, fast_size, slow_size, net)
    return t["rs_ag"] < t["fused"]


def analytic_choice(msg_bytes: float, fast_size: int, slow_size: int,
                    net: cm.NetworkSpec) -> ARChoice:
    """Best strategy under the alpha-beta model (ties break toward the
    fewest-latency-steps strategy by dict order: flat < hier_ring < hier_rd
    is not the right order, so we order candidates explicitly)."""
    times = predict_times(msg_bytes, fast_size, slow_size, net)
    # Tie-break order: fewest inter-phase latency steps first.
    order = ("hier_rd", "hier_rd_halving", "hier_ring", "flat")
    best = min(order, key=lambda s: times[s])
    rd_chunks = 1
    if best == "hier_rd" and slow_size > 1:
        rd_chunks = _rd_chunks_for(msg_bytes, fast_size)
    return ARChoice(strategy=best, rd_chunks=rd_chunks)


def predict_quant_times(msg_bytes: float, fast_size: int, slow_size: int,
                        net: cm.NetworkSpec) -> Dict[str, float]:
    """Predicted seconds per wire-quantization level.

    ``none`` is the best full-precision strategy at this size; int8/int4
    run the quantized hierarchical path (packed RS + quantized RD inter +
    packed AG) whose bandwidth terms shrink by the wire factor while its
    latency terms — and per-phase pack overhead — do not.  That asymmetry
    is the whole point: quantization wins only past the crossover where
    the transfer is bandwidth-bound (paper Sec. 4.3 frame, Flash-
    Communication payload model)."""
    t_none = min(predict_times(msg_bytes, fast_size, slow_size, net)
                 .values())
    return {
        "none": t_none,
        "int8": cm.t_quant_hier_allreduce(msg_bytes, slow_size, fast_size,
                                          net, 8),
        "int4": cm.t_quant_hier_allreduce(msg_bytes, slow_size, fast_size,
                                          net, 4),
    }


def analytic_quant_choice(msg_bytes: float, fast_size: int, slow_size: int,
                          net: cm.NetworkSpec, mode: str) -> ARChoice:
    """Dispatch entry for a quant-aware call site (``mode`` != "none").

    Forced modes ("int8"/"int4") always quantize — the user overrode the
    accuracy tradeoff — and route through hier_rd when a slow axis
    exists, since that is the topology the quantized path implements.
    ``"auto"`` climbs an accuracy ladder: each lossier level must beat
    the previous by >10% predicted time to be worth its extra error, so
    the lossless choice wins ties and int4 only appears where bandwidth
    savings are decisive."""
    base = analytic_choice(msg_bytes, fast_size, slow_size, net)
    if mode in ("int8", "int4"):
        strat = "hier_rd" if slow_size > 1 else base.strategy
        return ARChoice(strategy=strat, rd_chunks=1, quant=mode)
    t = predict_quant_times(msg_bytes, fast_size, slow_size, net)
    quant = "none"
    if t["int8"] < 0.9 * t["none"]:
        quant = "int8"
        if t["int4"] < 0.9 * t["int8"]:
            quant = "int4"
    if quant == "none":
        return base
    strat = "hier_rd" if slow_size > 1 else base.strategy
    return ARChoice(strategy=strat, rd_chunks=1, quant=quant)


# ---------------------------------------------------------------------------
# Dispatch table
# ---------------------------------------------------------------------------


def _bucket(msg_bytes: int) -> int:
    """Power-of-two message-size bucket (log2, clamped)."""
    return max(8, int(math.ceil(math.log2(max(1, int(msg_bytes))))))


def bucket_of(msg_bytes: int) -> int:
    """Public form of the table's message-size bucketing (log2 exponent).

    Lets callers report the operating point a workload *would* dispatch on
    (e.g. per-pool AR buckets in disaggregated serving metrics) without a
    mesh in the loop — the same exponent ``choose`` keys the table with.
    """
    return _bucket(msg_bytes)


def _key(msg_bytes: int, fast_size: int, slow_size: int,
         dtype: str) -> str:
    return f"b{_bucket(msg_bytes)}/f{fast_size}/s{slow_size}/{dtype}"


def _parse_key(key: str) -> Tuple[int, int, int, str]:
    """(bucket_bytes, fast_size, slow_size, dtype) back out of a table key.

    ``bucket_bytes`` is the bucket's representative size — the power-of-two
    upper bound ``2**b`` the key was bucketed to, NOT the original message
    size (which is lost to bucketing; every consumer must treat it as the
    bucket bound).  Round-trip invariant:
    ``_key(*_parse_key(k)) == k`` for every well-formed key ``k``.
    """
    b, f, s, dtype = key.split("/")
    return 2 ** int(b[1:]), int(f[1:]), int(s[1:]), dtype


_CHOICE_FIELDS = frozenset(f.name for f in dataclasses.fields(ARChoice))


@dataclasses.dataclass
class _Measurement:
    strategy: str
    seconds: float
    quant: str = "none"


class AutoTuner:
    """Per-call-site all-reduce dispatcher.

    Analytic predictions seed every lookup; measurements filed with
    :meth:`record` (a caller's own timings of each strategy, or
    production telemetry) override them after :meth:`refine`.
    Thread-safe for the trace-time lookup pattern.
    """

    def __init__(self, net: cm.NetworkSpec = cm.TPU_V5E):
        self.net = net
        self.table: Dict[str, ARChoice] = {}
        self.measurements: Dict[str, List[_Measurement]] = {}
        # trace-time lookup log: key -> times dispatched.  Lets a caller
        # that owns a tuner instance (e.g. one serving pool) prove which
        # message-size buckets its workload actually keyed the table on.
        self.lookups: Dict[str, int] = {}
        # sequence-parallel dispatch: key -> use RS+AG instead of the
        # fused all-reduce for that residual message size (PR 5 tentpole;
        # consulted by ``seq_parallel="auto"`` call sites at trace time).
        self.sp_table: Dict[str, bool] = {}
        self.sp_lookups: Dict[str, int] = {}
        self._lock = threading.Lock()

    # -- lookup ------------------------------------------------------------

    def choose(self, msg_bytes: int, fast_size: int, slow_size: int,
               dtype: str = "bfloat16", quant: str = "none") -> ARChoice:
        """Dispatch one call site.  ``quant`` is the ctx's ar_quant policy:
        "none" keys and seeds exactly as before (old persisted tables stay
        valid); any other policy gets its own key namespace via a dtype
        suffix (``bfloat16:qauto``) so quant-aware and plain dispatch never
        alias the same bucket."""
        kdtype = dtype if quant == "none" else f"{dtype}:q{quant}"
        key = _key(msg_bytes, fast_size, slow_size, kdtype)
        with self._lock:
            self.lookups[key] = self.lookups.get(key, 0) + 1
            hit = self.table.get(key)
            if hit is not None:
                return hit
            if quant == "none":
                choice = analytic_choice(msg_bytes, fast_size, slow_size,
                                         self.net)
            else:
                choice = analytic_quant_choice(msg_bytes, fast_size,
                                               slow_size, self.net, quant)
            self.table[key] = choice
            return choice

    def choose_sp(self, msg_bytes: int, fast_size: int, slow_size: int,
                  dtype: str = "bfloat16") -> bool:
        """Per-call-site sequence-parallel dispatch: True routes the
        residual through the RS+AG decomposition, False keeps the fused
        all-reduce.  Seeded analytically (:func:`analytic_sp_choice`);
        persisted entries override."""
        key = _key(msg_bytes, fast_size, slow_size, dtype)
        with self._lock:
            self.sp_lookups[key] = self.sp_lookups.get(key, 0) + 1
            hit = self.sp_table.get(key)
            if hit is None:
                hit = analytic_sp_choice(msg_bytes, fast_size, slow_size,
                                         self.net)
                self.sp_table[key] = hit
            return hit

    def lookup_buckets(self) -> List[int]:
        """Sorted message-size bucket exponents this tuner has dispatched
        on (one entry per distinct table key seen by :meth:`choose`)."""
        with self._lock:
            return sorted({int(k.split("/")[0][1:]) for k in self.lookups})

    def sp_lookup_buckets(self) -> List[int]:
        """Bucket exponents the SP dispatcher was consulted on (one entry
        per distinct key seen by :meth:`choose_sp`)."""
        with self._lock:
            return sorted({int(k.split("/")[0][1:])
                           for k in self.sp_lookups})

    # -- measurement refinement -------------------------------------------

    def record(self, msg_bytes: int, fast_size: int, slow_size: int,
               dtype: str, strategy: str, seconds: float,
               quant: str = "none",
               policy: Optional[str] = None) -> None:
        """File one measured (strategy, quant) latency.

        ``quant`` is the concrete wire level that was measured; ``policy``
        is the dispatch namespace to file it under and defaults to
        ``quant``.  A sweep tuning the ``"auto"`` policy measures concrete
        levels as candidates but files them all under ``policy="auto"`` so
        :meth:`refine` crowns one winner per auto-keyed bucket."""
        ns = quant if policy is None else policy
        kdtype = dtype if ns == "none" else f"{dtype}:q{ns}"
        key = _key(msg_bytes, fast_size, slow_size, kdtype)
        with self._lock:
            self.measurements.setdefault(key, []).append(
                _Measurement(strategy, seconds, quant))

    def refine(self) -> int:
        """Overwrite table entries with measured winners; returns the number
        of entries changed."""
        changed = 0
        with self._lock:
            for key, ms in self.measurements.items():
                best = min(ms, key=lambda m: m.seconds)
                prev = self.table.get(key)
                rd_chunks = 1
                if best.strategy == "hier_rd" and best.quant == "none":
                    # Recompute from the bucket bound, not from the
                    # previous entry: the analytic seed only sets chunks
                    # when it itself picked hier_rd.  (The original
                    # message size is gone — the bucket bound is the only
                    # coherent size to chunk on, same as ``choose``.)
                    # Quantized winners keep rd_chunks=1: the quantized
                    # slow exchange requantizes per step and does not
                    # pipeline chunks.
                    bucket_bytes, fast, slow, _ = _parse_key(key)
                    if slow > 1:
                        rd_chunks = _rd_chunks_for(bucket_bytes, fast)
                new = ARChoice(strategy=best.strategy, rd_chunks=rd_chunks,
                               quant=best.quant)
                if prev != new:
                    self.table[key] = new
                    changed += 1
        return changed

    # -- persistence -------------------------------------------------------

    def to_json(self) -> Dict:
        return {
            "version": TABLE_VERSION,
            "net": self.net.name,
            "table": {k: dataclasses.asdict(v)
                      for k, v in sorted(self.table.items())},
            "sp_table": dict(sorted(self.sp_table.items())),
        }

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=2, sort_keys=True)

    @classmethod
    def _degraded(cls, path: str, why: str) -> "AutoTuner":
        """Degraded-mode fallback for an unusable persisted table: warn
        and seed a fresh analytic tuner — a serving process must come up
        with the comm-model dispatch rather than crash on a bad file
        (DESIGN.md §11)."""
        warnings.warn(f"ar-table {path!r} unusable ({why}); degrading to "
                      f"analytic comm-model seeding", RuntimeWarning,
                      stacklevel=3)
        return cls()

    @classmethod
    def load(cls, path: str) -> "AutoTuner":
        """Load a persisted table, degrading (never raising) on a corrupt
        or wrong-schema file: unreadable JSON, a non-object document, or
        an unknown schema version falls back to a fresh analytic tuner
        with a ``RuntimeWarning``; individually malformed table entries
        are dropped (counted in the warning) and the rest kept.

        Tables saved by older versions carry fields of options since
        removed (the int8 slow-exchange flag, in every entry).  Such a
        field left off selects the path that still exists and is ignored;
        one set on names a removed path, and its entry is dropped."""
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            return cls._degraded(path, f"unreadable: {e}")
        if not isinstance(doc, dict):
            return cls._degraded(path, f"JSON {type(doc).__name__}, "
                                       f"not an object")
        version = doc.get("version", 1)
        if version != TABLE_VERSION:
            return cls._degraded(path, f"schema version {version!r} != "
                                       f"{TABLE_VERSION}")
        net = cm.NETWORKS.get(doc.get("net", "tpu_v5e"), cm.TPU_V5E)
        t = cls(net)
        table = doc.get("table", {})
        sp_table = doc.get("sp_table", {})
        if not isinstance(table, dict) or not isinstance(sp_table, dict):
            return cls._degraded(path, "table/sp_table not objects")
        dropped = 0
        for k, v in table.items():
            try:
                _parse_key(k)   # malformed keys never dispatch — reject
                v = dict(v)
                gone = [f for f in v if f not in _CHOICE_FIELDS]
                if any(v.pop(f) for f in gone):
                    raise ValueError(f"removed option set: {gone}")
                c = ARChoice(**v)
                if c.strategy not in DISPATCHABLE:
                    raise ValueError(f"unknown strategy {c.strategy!r}")
                if int(c.rd_chunks) < 1:
                    raise ValueError(f"rd_chunks {c.rd_chunks!r} < 1")
                if c.quant not in QUANT_LEVELS:
                    raise ValueError(f"unknown quant {c.quant!r}")
            except (TypeError, ValueError, AttributeError, IndexError):
                dropped += 1
                continue
            t.table[k] = c
        for k, v in sp_table.items():
            try:
                int(str(k).split("/")[0][1:])   # "b{bucket}/..." shape
            except (TypeError, ValueError, IndexError):
                dropped += 1
                continue
            t.sp_table[k] = bool(v)
        if dropped:
            warnings.warn(f"ar-table {path!r}: dropped {dropped} "
                          f"malformed or removed-path "
                          f"entr{'y' if dropped == 1 else 'ies'}"
                          f"; kept {len(t.table) + len(t.sp_table)}",
                          RuntimeWarning, stacklevel=2)
        return t


# ---------------------------------------------------------------------------
# Process-wide active tuner (what ar_strategy="auto" resolves against)
# ---------------------------------------------------------------------------

_ACTIVE = AutoTuner()


def active() -> AutoTuner:
    return _ACTIVE


def install(tuner: AutoTuner) -> AutoTuner:
    """Swap the process-wide tuner (returns the previous one)."""
    global _ACTIVE
    prev, _ACTIVE = _ACTIVE, tuner
    return prev


def install_from_path(path: Optional[str]) -> AutoTuner:
    """Install a persisted table if ``path`` exists; else keep/seed default.

    Also honors the ``REPRO_AR_TABLE`` environment variable when ``path`` is
    None, so deployments can point every entry point at one tuned table."""
    if path is None:
        path = os.environ.get("REPRO_AR_TABLE")
    if path and os.path.exists(path):
        install(AutoTuner.load(path))
    return _ACTIVE


def tuner_for(path: Optional[Union[str, AutoTuner]]) -> AutoTuner:
    """Resolve (without installing) the tuner a build should capture:
    an :class:`AutoTuner` instance passes through untouched (per-pool
    tables in disaggregated serving), an explicit path loads, else
    ``REPRO_AR_TABLE``, else the active default."""
    if isinstance(path, AutoTuner):
        return path
    if path is None:
        path = os.environ.get("REPRO_AR_TABLE")
    if path and os.path.exists(path):
        return AutoTuner.load(path)
    return _ACTIVE


@contextlib.contextmanager
def using(tuner: AutoTuner):
    """Temporarily make ``tuner`` the active dispatcher.

    Step builders wrap their (traced) bodies with this so each built step
    resolves 'auto' against the table captured at build time, even when
    jit defers tracing past a later build that installed a different
    table."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = tuner
    try:
        yield tuner
    finally:
        _ACTIVE = prev


def resolve(ctx, msg_bytes: int, fast_size: int, slow_size: int,
            dtype: str):
    """Concretize ctx.ar_strategy == 'auto' for one call site.  The ctx's
    ar_quant policy flows into the lookup so quant-aware strategy picks
    (and, under ``ar_quant="auto"``, the per-bucket level itself) come
    from the same table."""
    choice = _ACTIVE.choose(int(msg_bytes), fast_size, slow_size,
                            str(dtype),
                            quant=getattr(ctx, "ar_quant", "none"))
    return choice.apply(ctx)


def resolve_sp(msg_bytes: int, fast_size: int, slow_size: int,
               dtype: str) -> bool:
    """Concretize ``seq_parallel="auto"`` for one prefill call site against
    the active tuner (trace-time, like :func:`resolve`)."""
    return _ACTIVE.choose_sp(int(msg_bytes), fast_size, slow_size,
                             str(dtype))


__all__ = [
    "ARChoice", "AutoTuner", "predict_times", "analytic_choice",
    "predict_sp_times", "analytic_sp_choice",
    "predict_quant_times", "analytic_quant_choice", "QUANT_LEVELS",
    "active", "install", "install_from_path", "tuner_for", "using",
    "resolve", "resolve_sp", "bucket_of", "DISPATCHABLE",
    "TABLE_VERSION",
]
