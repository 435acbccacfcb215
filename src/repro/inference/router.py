"""The multi-replica serving router (DESIGN.md §13).

Single-replica tensor parallelism hits the communication wall the paper
quantifies well before the hardware runs out (Sec. 5's strong-scaling
study): past the AR mitigations in PRs 1/5/7, the remaining throughput
lever is *data parallelism over replicas*.  This module is that tier: a
:class:`Router` load-balances a request trace over N self-contained
replicas — each a :class:`~.scheduler.ContinuousBatcher` or
:class:`~.disagg.DisaggCoordinator` with its own mesh (disjoint device
group), AR table, and KV cache — and owns admission *placement*, while
each replica keeps owning its internal scheduling.

Design invariants:

* **Placement is a pure function of a load snapshot.**  Every policy is
  ``f(loads: List[ReplicaLoad], rr: int) -> int`` over per-replica
  :class:`ReplicaLoad` snapshots, so policies unit-test on synthetic
  queue states with no engine behind them.  Load is measured in *queue
  depth and estimated cost on the logical step clock* — never wall
  clock — so placement is deterministic and a trace replays bit-identically
  across runs and machines (wall time would make placement a function of
  CI jitter).
* **Replica-affine preemption recovery.**  A preempted request re-admits
  through its own replica's requeue (``ContinuousBatcher.tick`` admits
  requeue-first; the disagg coordinator splices decode evictions back
  into its own pending queue).  The router never re-places a preempted
  request — its KV/recompute context and sampling chain live on the
  replica that admitted it.
* **Fleet == N independent singles.**  Replicas never interact, so a
  ``round_robin`` fleet is *token-identical per request* to N standalone
  replicas each fed its own arrival-index subset (asserted in
  tests/test_router.py and tests/dist_cases/case_router.py).
* **Per-replica fault isolation.**  ``build_replica`` folds the replica
  id into the fault-plan seed, so one replica's injected drops/stalls
  never mirror onto another's requests.

The ``ttft_aware`` policy estimates each queued prompt's prefill cost
with the paper's analytic machinery (``core.comm_model`` ring/tree AR
model + chip GEMM roofline from ``inference.simulator``): per-layer
projection flops over the chip's sustained throughput, plus two
all-reduces per layer at the replica's TP layout when tp > 1.  Units are
seconds, but only the *ordering* matters for placement.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from .scheduler import Request, ServeMetrics
from .spec import ReplicaSpec, ServeSpec, build_replica

# ---------------------------------------------------------------------------
# Load snapshots and placement policies
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ReplicaLoad:
    """One replica's admission-relevant state at a placement decision.

    All quantities live on the logical step clock / token axis —
    deterministic, replayable, CI-stable (see module docstring).
    """
    queue_depth: int            # due requests queued, not yet admitted
    queued_prompt_tokens: int   # sum of queued prompt lengths
    active: int                 # occupied decode slots (incl. requeue)
    slots: int                  # decode slot capacity
    active_remaining: int       # decode tokens left across active slots
    est_queue_cost: float = 0.0   # est. prefill seconds queued ahead
    est_active_cost: float = 0.0  # est. drain seconds of active decodes


def place_round_robin(loads: Sequence[ReplicaLoad], rr: int) -> int:
    """Arrival index modulo fleet size — the parity-bearing baseline."""
    return rr % len(loads)


def place_least_queue(loads: Sequence[ReplicaLoad], rr: int) -> int:
    """Fewest requests in flight (queued + active); ties to the lowest
    index so placement is deterministic."""
    return min(range(len(loads)),
               key=lambda i: (loads[i].queue_depth + loads[i].active, i))


def place_ttft_aware(loads: Sequence[ReplicaLoad], rr: int) -> int:
    """Smallest estimated wait-to-first-token: the prefill cost of the
    work queued ahead, plus — when every slot is busy — the estimated
    drain cost of the active decodes the arrival must wait behind.
    Queue depth breaks cost ties (two empty replicas look identical)."""
    def key(i: int):
        l = loads[i]
        c = l.est_queue_cost
        if l.slots and l.active >= l.slots:
            c += l.est_active_cost
        return (c, l.queue_depth + l.active, i)
    return min(range(len(loads)), key=key)


POLICIES: Dict[str, Callable[[Sequence[ReplicaLoad], int], int]] = {
    "round_robin": place_round_robin,
    "least_queue": place_least_queue,
    "ttft_aware": place_ttft_aware,
}


# ---------------------------------------------------------------------------
# Analytic prefill cost (comm_model + chip roofline)
# ---------------------------------------------------------------------------


def prefill_cost_model(spec: ReplicaSpec, net=None,
                       chip=None) -> Callable[[int], float]:
    """``spec`` -> ``f(prompt_tokens) -> estimated prefill seconds``.

    Compute term: per-layer projection GEMM flops (tile-floor applied)
    over the chip's sustained bf16 throughput, split ``tp`` ways.  Comm
    term (tp > 1): two all-reduces per layer of the activation message
    ``S * d_model * itemsize`` at the best of the modeled algorithms for
    the replica's (pods x tp/pods) layout.  Deterministic by
    construction — pure arithmetic on the spec.
    """
    from ..configs import get_config, get_smoke
    from ..core.comm_model import NETWORKS, nccl_model_best
    from .simulator import CHIP_FOR_NET, V5E, _layer_gemm_flops
    cfg = get_smoke(spec.arch) if spec.smoke else get_config(spec.arch)
    tp = spec.prefill_tp if spec.disagg else spec.tp
    pods = spec.prefill_pods if spec.disagg else spec.pods
    if net is None:
        net = NETWORKS["tpu_v5e"]
    if chip is None:
        chip = CHIP_FOR_NET.get(net.name, V5E)
    itemsize = 2  # bf16 activations
    def cost(s_tokens: int) -> float:
        flops = cfg.n_layers * _layer_gemm_flops(cfg, s_tokens,
                                                 chip.gemm_tile_m)
        t = flops / (tp * chip.flops_bf16 * chip.efficiency)
        if tp > 1:
            msg = 2.0 * s_tokens * cfg.d_model * itemsize
            _, t_ar = nccl_model_best(msg, pods, tp // pods, net)
            t += cfg.n_layers * t_ar
        return t
    return cost


# ---------------------------------------------------------------------------
# Fleet metrics
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RouterMetrics:
    """Fleet-level aggregation: per-replica metrics plus their lossless
    merge (percentiles recomputed from retained samples — never an
    average of per-replica p99s) and placement accounting."""
    replicas: int
    policy: str
    placements: List[int]          # requests placed per replica
    load_imbalance: float          # max/mean of placements (1.0 = even)
    fleet: Any                     # ServeMetrics | DisaggMetrics merge
    per_replica: List[Any]

    def to_dict(self) -> Dict[str, Any]:
        return {
            "replicas": self.replicas,
            "policy": self.policy,
            "placements": list(self.placements),
            "load_imbalance": self.load_imbalance,
            "fleet": self.fleet.to_dict(),
            "per_replica": [m.to_dict() for m in self.per_replica],
        }


# ---------------------------------------------------------------------------
# The router
# ---------------------------------------------------------------------------


class Router:
    """Front-end tier over N self-contained replicas.

    Drives the fleet on one shared logical step clock: each tick, due
    arrivals are placed (policy over :class:`ReplicaLoad` snapshots)
    onto per-replica queues the router owns, then every replica runs one
    ``tick(queue, now)`` — the same entry point ``run`` uses standalone,
    so a routed replica schedules exactly like a single one.
    """

    def __init__(self, replicas: Sequence[Any], policy: str = "round_robin",
                 cost_fn: Optional[Callable[[int], float]] = None):
        if not replicas:
            raise ValueError("Router needs at least one replica")
        if policy not in POLICIES:
            raise ValueError(f"unknown router policy {policy!r} "
                             f"(one of {tuple(POLICIES)})")
        self.replicas = list(replicas)
        kinds = {hasattr(r, "decode") for r in self.replicas}
        if len(kinds) > 1:
            raise ValueError("heterogeneous fleet: all replicas must be "
                             "batchers or all coordinators")
        self.policy = policy
        # token-count proxy when no spec/cost model: est cost ~ tokens.
        # monotone in prompt length, which is all ttft_aware needs.
        self.cost_fn = cost_fn if cost_fn is not None else float
        self.queues: List[List[Request]] = [[] for _ in self.replicas]
        self.placements = [0] * len(self.replicas)
        self.assigned: List[List[Request]] = [[] for _ in self.replicas]
        self._rr = 0

    @classmethod
    def from_spec(cls, spec: ServeSpec, *, ap=None, params=None,
                  devices=None) -> "Router":
        """Build the fleet a ``ServeSpec`` describes: N replicas from one
        template, each on its own disjoint contiguous device group (carved
        from ``devices``, default ``jax.devices()``) with its weights and
        cache placed there, each with an independently-seeded fault
        schedule (``replica_id`` folds into the plan seed)."""
        from ..parallel.topology import replica_device_groups
        spec.validate()
        rspec = spec.replica
        groups = replica_device_groups(spec.replicas, rspec.device_need,
                                       devices)
        reps = [build_replica(rspec, ap=ap, params=params,
                              devices=groups[i], replica_id=i)
                for i in range(spec.replicas)]
        return cls(reps, policy=spec.router_policy,
                   cost_fn=prefill_cost_model(rspec))

    # -- load snapshot -------------------------------------------------------

    def _load(self, i: int) -> ReplicaLoad:
        rep = self.replicas[i]
        q = self.queues[i]
        dec = rep.decode if hasattr(rep, "decode") else rep
        # in-flight disagg handoffs count as queued-ahead work
        inflight = len(rep._ready) if hasattr(rep, "_ready") else 0
        active = sum(a is not None for a in dec.active) + len(dec._requeue)
        remaining = sum(int(dec.remaining[s])
                        for s, a in enumerate(dec.active) if a is not None)
        q_tokens = sum(len(r.prompt) for r in q)
        est_q = sum(self.cost_fn(len(r.prompt)) for r in q) \
            + inflight * self.cost_fn(1)
        # decode drains ~1 token per active slot per step; cost_fn(1) is
        # the single-token forward estimate for one such step
        steps_to_free = min((int(dec.remaining[s])
                             for s, a in enumerate(dec.active)
                             if a is not None), default=0)
        est_a = steps_to_free * self.cost_fn(1)
        return ReplicaLoad(
            queue_depth=len(q) + inflight, queued_prompt_tokens=q_tokens,
            active=active, slots=dec.slots, active_remaining=remaining,
            est_queue_cost=est_q, est_active_cost=est_a)

    def _place(self, req: Request) -> int:
        loads = [self._load(i) for i in range(len(self.replicas))]
        i = POLICIES[self.policy](loads, self._rr)
        self._rr += 1
        self.placements[i] += 1
        self.queues[i].append(req)
        self.assigned[i].append(req)
        return i

    # -- trace replay --------------------------------------------------------

    def run(self, requests: List[Request],
            max_steps: int = 100000) -> List[Request]:
        """Replay a trace over the fleet (same contract as
        ``ContinuousBatcher.run``): one shared logical clock, placement
        at arrival, every replica ticked every step, drained when every
        queue, requeue, and slot across the fleet is empty."""
        waiting = sorted(requests, key=lambda r: r.arrival_s)
        qi = 0
        now = 0.0
        self.queues = [[] for _ in self.replicas]
        self.placements = [0] * len(self.replicas)
        self.assigned = [[] for _ in self.replicas]
        self._rr = 0
        for rep in self.replicas:
            if hasattr(rep, "begin_run"):
                rep.begin_run()
            else:
                rep.reset_run_stats()
        wall0 = time.perf_counter()
        for _ in range(max_steps):
            while qi < len(waiting) and waiting[qi].arrival_s <= now:
                self._place(waiting[qi])
                qi += 1
            if qi >= len(waiting) and all(
                    rep.drained(q)
                    for rep, q in zip(self.replicas, self.queues)):
                break
            for rep, q in zip(self.replicas, self.queues):
                rep.tick(q, now)
            now += 1.0
        wall = time.perf_counter() - wall0
        # fleet wall: replicas share the loop, so each gets the same wall
        for rep in self.replicas:
            if hasattr(rep, "decode"):
                rep._wall = wall
                rep.decode._wall_run = wall
            else:
                rep._wall_run = wall
        return requests

    # -- metrics -------------------------------------------------------------

    def metrics(self, requests: List[Request]) -> RouterMetrics:
        from .disagg import DisaggMetrics
        per = [rep.metrics(self.assigned[i])
               for i, rep in enumerate(self.replicas)]
        cls = DisaggMetrics if hasattr(self.replicas[0], "decode") \
            else ServeMetrics
        fleet = cls.merge(per)
        mean = sum(self.placements) / len(self.placements)
        imb = max(self.placements) / mean if mean else 0.0
        return RouterMetrics(
            replicas=len(self.replicas), policy=self.policy,
            placements=list(self.placements), load_imbalance=imb,
            fleet=fleet, per_replica=per)


__all__ = ["Router", "RouterMetrics", "ReplicaLoad", "POLICIES",
           "place_round_robin", "place_least_queue", "place_ttft_aware",
           "prefill_cost_model"]
