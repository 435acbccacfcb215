"""Deterministic fault injection for the serving stack (DESIGN.md §11).

The paper's operating point is multi-node (Llama 3.1 405B over
Slingshot/InfiniBand), where transient faults — dropped or corrupted KV
transfers, straggler pools, non-finite activations, allocator pressure
bursts — are routine operating conditions, not exceptional ones.  This
module is the *fault model* side of the robustness layer: a seedable
:class:`FaultPlan` describing per-kind fault rates, and a
:class:`FaultInjector` the serving loops consult at explicit hook points
(never monkeypatching):

* ``ContinuousBatcher.step`` / ``_spec_step`` — ``poison_slot`` (NaN
  injected into a slot's live KV so non-finite logits arise *on device*),
  ``oom_burst`` (the allocator behaves as if the free list ran dry),
  ``straggle`` (wall-clock decode delay; the logical clock is untouched);
* ``DisaggCoordinator.run`` — ``corrupt_handoff`` (bundle payload damaged
  in flight; detected by the :class:`~repro.inference.kv_cache.KVBundle`
  checksum at splice time), ``drop_handoff`` (the transfer attempt is
  lost; retried with backoff), ``prefill_stalled`` / ``decode_stalled``
  (a pool freezes for whole windows of ``stall_steps`` ticks).

Determinism contract: every decision is a pure hash of
``(plan.seed, kind, ids...)`` — no RNG state, no wall clock — so a fault
schedule replays bit-identically, and the event set at rate ``r1`` is a
**subset** of the event set at ``r2 >= r1`` for the same seed/ids (the
decision is ``hash_unit < rate``).  That superset property is what lets
``tests/test_faults.py::test_goodput_monotone_in_fault_rate`` assert
goodput degrades monotonically in the fault rate.

The recovery obligations on the consumer side (retry/backoff, re-prefill
fallback, quarantine + recompute, deadline shedding) live with the
consumers; the invariant they jointly enforce is: **every non-shed greedy
request's tokens are bitwise-identical to the fault-free trace, and shed
requests are always reported, never silently dropped** (docs/robustness.md).
"""
from __future__ import annotations

import dataclasses
import json
import os
import zlib
from typing import Any, Dict, Optional

import numpy as np

# fault kinds an injector counts (stats() keys, in reporting order)
FAULT_KINDS = ("handoff_drop", "handoff_corrupt", "prefill_stall",
               "decode_stall", "straggler", "nan_logits", "oom")


def hash_unit(seed: int, kind: str, *ids: int) -> float:
    """Deterministic uniform draw in [0, 1) from (seed, kind, ids).

    crc32 of the repr — stable across processes and platforms (unlike
    ``hash``), cheap enough for per-step hooks, and stateless so the
    fault schedule is independent of evaluation order.
    """
    h = zlib.crc32(repr((int(seed), kind) + tuple(int(i) for i in ids))
                   .encode())
    return h / 2.0 ** 32


@dataclasses.dataclass
class FaultPlan:
    """Seedable description of a fault workload (all rates in [0, 1]).

    * ``handoff_drop``    — per handoff *transfer attempt* (rid, attempt):
      the attempt is lost; the coordinator retries with backoff.
    * ``handoff_corrupt`` — per *prefill* of a request (rid, prefill#):
      the bundle payload is flipped in flight; the checksum catches it at
      splice time and the coordinator falls back to re-prefill.
    * ``prefill_stall`` / ``decode_stall`` — per window of
      ``stall_steps`` ticks: the pool freezes for the whole window
      (crash-and-recover for N steps).
    * ``straggler``       — per decode step: an artificial wall-clock
      delay of ``straggler_s`` (logical clock untouched — latency noise,
      never a token change).
    * ``nan_logits``      — per (request, progress): a non-finite value
      is poked into the request's live KV once it has emitted that many
      tokens, so the *device* produces non-finite logits and the
      batcher's quarantine guard must catch it.  Keyed on request
      identity + progress (never the wall step) so the event set — and
      the decode work each quarantine destroys — is invariant to
      scheduling shifts; each key fires at most once, so the
      quarantine-recompute replay is not re-poisoned into a livelock.
    * ``oom``             — per step: allocator growth behaves as if the
      free pool ran dry (burst); growing slots are evicted and recomputed.
    """

    seed: int = 0
    handoff_drop: float = 0.0
    handoff_corrupt: float = 0.0
    prefill_stall: float = 0.0
    decode_stall: float = 0.0
    stall_steps: int = 3
    straggler: float = 0.0
    straggler_s: float = 0.0
    nan_logits: float = 0.0
    oom: float = 0.0

    def __post_init__(self):
        for f in ("handoff_drop", "handoff_corrupt", "prefill_stall",
                  "decode_stall", "straggler", "nan_logits", "oom"):
            v = float(getattr(self, f))
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"fault rate {f}={v} outside [0, 1]")
            setattr(self, f, v)
        if int(self.stall_steps) < 1:
            raise ValueError(f"stall_steps must be >= 1, got "
                             f"{self.stall_steps}")
        self.stall_steps = int(self.stall_steps)
        self.seed = int(self.seed)

    @property
    def any_faults(self) -> bool:
        return any(getattr(self, f) > 0.0 for f in
                   ("handoff_drop", "handoff_corrupt", "prefill_stall",
                    "decode_stall", "straggler", "nan_logits", "oom"))

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def parse(cls, spec: str) -> "FaultPlan":
        """Build a plan from a ``k=v,k=v`` string or a JSON file path
        (the ``--fault-plan`` flag accepts either)."""
        if os.path.exists(spec):
            with open(spec) as f:
                doc = json.load(f)
            if not isinstance(doc, dict):
                raise ValueError(f"fault plan {spec!r} must hold a JSON "
                                 f"object, got {type(doc).__name__}")
            return cls(**doc)
        kw: Dict[str, Any] = {}
        fields = {f.name: f.type for f in dataclasses.fields(cls)}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            if "=" not in part:
                raise ValueError(f"bad fault-plan entry {part!r} "
                                 f"(want key=value)")
            k, v = part.split("=", 1)
            k = k.strip()
            if k not in fields:
                raise ValueError(f"unknown fault-plan key {k!r}; known: "
                                 f"{sorted(fields)}")
            kw[k] = int(v) if k in ("seed", "stall_steps") else float(v)
        return cls(**kw)


class FaultInjector:
    """Hook-point decisions + injected/observed-event counters.

    One injector instance serves one run; ``reset_stats`` re-arms it for
    a fresh trace (decisions are stateless, so a reset replays the same
    schedule).  ``counts`` tallies decisions that fired; consumers own
    the *recovery* counters (retries, sheds, quarantines) in their
    metrics.
    """

    def __init__(self, plan: FaultPlan):
        self.plan = plan
        self.counts: Dict[str, int] = {k: 0 for k in FAULT_KINDS}
        self._nan_fired: set = set()

    # -- bookkeeping -------------------------------------------------------

    def reset_stats(self) -> None:
        self.counts = {k: 0 for k in FAULT_KINDS}
        self._nan_fired = set()

    def stats(self) -> Dict[str, int]:
        return dict(self.counts)

    def _fire(self, kind: str, rate: float, *ids: int) -> bool:
        if rate <= 0.0:
            return False
        hit = hash_unit(self.plan.seed, kind, *ids) < rate
        if hit:
            self.counts[kind] += 1
        return hit

    # -- handoff-path hooks (DisaggCoordinator) ----------------------------

    def drop_handoff(self, rid: int, attempt: int) -> bool:
        """Lose transfer attempt ``attempt`` of request ``rid``?"""
        return self._fire("handoff_drop", self.plan.handoff_drop,
                          rid, attempt)

    def corrupt_handoff(self, rid: int, prefill_no: int) -> bool:
        """Damage the bundle produced by ``rid``'s ``prefill_no``-th
        prefill?  (Keyed per prefill, not per attempt: the same corrupt
        payload stays corrupt across retries — only a re-prefill can
        produce a clean bundle.)"""
        return self._fire("handoff_corrupt", self.plan.handoff_corrupt,
                          rid, prefill_no)

    def prefill_stalled(self, step: float) -> bool:
        """Is the prefill pool frozen at logical ``step``?  Stalls occupy
        whole windows of ``stall_steps`` ticks (crash for N steps)."""
        return self._fire("prefill_stall", self.plan.prefill_stall,
                          int(step) // self.plan.stall_steps)

    def decode_stalled(self, step: float) -> bool:
        """Is the decode pool frozen at logical ``step``?"""
        return self._fire("decode_stall", self.plan.decode_stall,
                          int(step) // self.plan.stall_steps)

    # -- decode-path hooks (ContinuousBatcher) -----------------------------

    def straggle(self, step: float) -> float:
        """Wall-clock delay (seconds; 0.0 = none) for this decode step."""
        if self._fire("straggler", self.plan.straggler, int(step)):
            return max(self.plan.straggler_s, 0.0)
        return -1.0

    def poison_slot(self, rid: int, emitted: int) -> bool:
        """Poke a non-finite value into request ``rid``'s live KV now
        that it has emitted ``emitted`` tokens?  Fire-once per
        (rid, emitted): the quarantine-recompute replay walks the same
        progress values again and must not be re-poisoned forever."""
        if self.plan.nan_logits <= 0.0:
            return False
        key = (int(rid), int(emitted))
        if key in self._nan_fired:
            return False
        if hash_unit(self.plan.seed, "nan_logits", *key) \
                < self.plan.nan_logits:
            self._nan_fired.add(key)
            self.counts["nan_logits"] += 1
            return True
        return False

    def oom_burst(self, step: float) -> bool:
        """Does allocator growth fail for the whole logical ``step``?"""
        return self._fire("oom", self.plan.oom, int(step))

    # -- payload damage ----------------------------------------------------

    @staticmethod
    def corrupt_bundle(bundle) -> None:
        """Flip one K element of a (sealed) bundle in place — the
        in-flight bit-flip the splice-time checksum must catch.  The
        perturbation is sign+magnitude (not NaN): silent corruption, the
        hard case — only the checksum can see it."""
        k = np.array(bundle.k)   # private copy: never alias a shared ref
        idx = (0,) * k.ndim
        k[idx] = -k[idx] + np.asarray(1.0, dtype=k.dtype)
        bundle.k = k


__all__ = ["FaultPlan", "FaultInjector", "FAULT_KINDS", "hash_unit"]
