"""The unified serving configuration API: ``ReplicaSpec`` / ``ServeSpec``.

Before this module, a serving deployment was ~25 keyword arguments
threaded in parallel through ``ContinuousBatcher.__init__``,
``PrefillPool``, ``DisaggCoordinator``, ``InferenceEngine`` and every
driver/test/benchmark that built one — and the incompatible-combo
rejections lived twice (CLI parse time and builder layer), drifting
apart.  This module makes the deployment a *value*:

* :class:`ReplicaSpec` — one self-contained serving replica: model,
  mesh layout (tp/pods or per-pool layouts under ``disagg``), AR knobs,
  KV layout, admission, sampling, speculation, robustness.  Frozen,
  hashable, JSON round-trippable.
* :class:`ServeSpec` — a deployment: ``mode`` (batch | trace), the
  replica template, the replica count, and the router placement policy.
* :meth:`ServeSpec.validate` — the single home of combo validation.
  The CLI, the factories below, and router-constructed replicas all
  call it, so every layer rejects identically, naming spec fields.
* :func:`build_replica` — the one factory that turns a ``ReplicaSpec``
  into a live ``ContinuousBatcher`` (colocated) or ``DisaggCoordinator``
  (``disagg=True``), used by ``launch.serve``, ``inference.router``,
  tests and benchmarks alike.  ``build_engine`` / ``build_prefill_pool``
  cover the batch engine and direct pool construction.

Serializability is the point: a router can ship a spec to construct a
replica, a bench can log the exact deployment next to its numbers, and
``ServeSpec.from_json(spec.to_json()) == spec`` holds for every CLI
combination (asserted in CI).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Optional

from ..configs import ARCH_IDS
from ..core.pcontext import AR_STRATEGIES, AR_QUANT_MODES, SEQ_PARALLEL_MODES

ROUTER_POLICIES = ("round_robin", "least_queue", "ttft_aware")

ADMIT_MODES = ("full", "chunked")
SPEC_MODES = (None, "ngram", "draft", "replay")
SERVE_MODES = ("batch", "trace")
PREFIX_MODES = ("off", "on")


class SpecError(ValueError):
    """An invalid ``ServeSpec``/``ReplicaSpec`` field combination.

    Raised by :meth:`ServeSpec.validate` — the same exception at CLI
    parse time, in the factories, and for router-constructed replicas.
    """


@dataclasses.dataclass(frozen=True)
class ReplicaSpec:
    """One self-contained serving replica (see module docstring)."""
    arch: str
    smoke: bool = True
    # -- mesh layout ------------------------------------------------------
    tp: int = 1
    pods: int = 1
    # -- all-reduce knobs (paper Sec. 4; DESIGN.md §3/§10/§12) ------------
    ar_strategy: str = "flat"
    ar_table: Optional[str] = None      # persisted autotune table path
    overlap: bool = False
    seq_parallel: str = "off"
    ar_quant: str = "none"
    # -- KV layout / admission -------------------------------------------
    slots: int = 4
    s_max: int = 128
    block_size: int = 0
    n_blocks: Optional[int] = None
    kv_quant: bool = False
    admit_mode: str = "full"
    admit_chunk: int = 32
    # -- prefix sharing (DESIGN.md §14) -----------------------------------
    prefix_cache: str = "off"
    prefix_capacity: Optional[int] = None   # max trie-pinned blocks
    # -- sampling ---------------------------------------------------------
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    # -- step-builder knobs -----------------------------------------------
    scan_layers: bool = True
    fsdp_serve: bool = False
    # -- speculative decoding (DESIGN.md §8) ------------------------------
    spec_mode: Optional[str] = None
    spec_k: int = 4
    spec_adaptive: bool = False
    draft_arch: str = "llama3.2-1b"
    spec_autodisable_after: int = 0
    # -- robustness (DESIGN.md §11) ---------------------------------------
    fault_plan: Optional[str] = None    # 'k=v,...' string or JSON path
    deadline_ms: Optional[float] = None  # 1 logical step = 1 ms
    # -- disaggregated prefill/decode pools (DESIGN.md §9) ----------------
    disagg: bool = False
    prefill_tp: int = 1
    prefill_pods: int = 1
    decode_tp: int = 1
    decode_pods: int = 1
    prefill_ar_table: Optional[str] = None
    decode_ar_table: Optional[str] = None
    # pool KV layout override: None = inherit ``block_size``; 0 forces a
    # dense pool in front of a paged decode pool (the bundles are layout
    # independent, so any combination hands off)
    prefill_block_size: Optional[int] = None
    prefill_per_step: int = 1
    max_handoff_retries: int = 3
    retry_backoff: float = 1.0
    max_ready: Optional[int] = None
    max_reprefills: int = 2

    # -- derived ----------------------------------------------------------

    @property
    def device_need(self) -> int:
        """Devices one replica of this spec occupies (its mesh carve
        width): the TP degree, or the wider pool under ``disagg`` (the
        pools run sequentially per tick and may share the group)."""
        return max(self.prefill_tp, self.decode_tp) if self.disagg \
            else self.tp

    def validate(self, mode: str = "trace") -> "ReplicaSpec":
        """Reject invalid field combinations (raises :class:`SpecError`).

        ``mode`` is the deployment mode the replica will serve under —
        several combos are trace-mode only.  Returns ``self`` so call
        sites can chain ``spec.validate().…``.
        """
        def bad(msg: str) -> None:
            raise SpecError(msg)

        if mode not in SERVE_MODES:
            bad(f"unknown mode={mode!r} (one of {SERVE_MODES})")
        if self.arch not in ARCH_IDS:
            bad(f"unknown arch={self.arch!r}")
        if self.ar_strategy not in AR_STRATEGIES:
            bad(f"unknown ar_strategy={self.ar_strategy!r}")
        if self.seq_parallel not in SEQ_PARALLEL_MODES:
            bad(f"unknown seq_parallel={self.seq_parallel!r}")
        if self.ar_quant not in AR_QUANT_MODES:
            bad(f"unknown ar_quant={self.ar_quant!r}")
        if self.admit_mode not in ADMIT_MODES:
            bad(f"unknown admit_mode={self.admit_mode!r}")
        if self.spec_mode not in SPEC_MODES:
            bad(f"unknown spec_mode={self.spec_mode!r}")
        if self.slots < 1:
            bad(f"slots={self.slots} must be >= 1")
        if self.tp < 1 or self.pods < 1:
            bad(f"tp={self.tp}/pods={self.pods} must be >= 1")
        if self.tp % self.pods:
            bad(f"tp={self.tp} not divisible by pods={self.pods}")
        if self.admit_mode == "chunked" and self.s_max % self.admit_chunk:
            bad(f"s_max={self.s_max} must be a multiple of "
                f"admit_chunk={self.admit_chunk}")
        if self.spec_mode and self.spec_k < 1:
            bad(f"spec_k must be >= 1, got spec_k={self.spec_k}")
        if self.ar_quant == "auto" and self.ar_strategy != "auto":
            bad("ar_quant='auto' rides the per-call-site autotuner: it "
                "requires --ar-strategy auto / ar_strategy='auto' (got "
                f"ar_strategy={self.ar_strategy!r})")
        if mode == "batch":
            if self.spec_adaptive:
                bad("spec_adaptive is trace-mode only (the batch engine "
                    "runs a fixed spec_k)")
            if self.fault_plan or self.deadline_ms is not None:
                bad("fault_plan/deadline_ms are trace-mode only (the "
                    "batch engine has no recovery machinery)")
            if self.disagg:
                bad("disagg is trace-mode only")
            if self.kv_quant:
                bad("kv_quant is trace-mode only (the batch engine's "
                    "prefill builds an fp cache)")
            if self.block_size and self.tp > 1:
                bad("block_size with mode='batch' is local-path only "
                    "(use mode='trace' for mesh-path paging)")
            if self.prefix_cache != "off":
                bad("prefix_cache is trace-mode only (admission-time "
                    "prefix splicing; the batch engine prefills once)")
        if self.prefix_cache not in PREFIX_MODES:
            bad(f"unknown prefix_cache={self.prefix_cache!r} (one of "
                f"{PREFIX_MODES})")
        if self.prefix_cache == "on":
            # ordered before the kv_quant block so a prefix_cache +
            # kv_quant combo is rejected naming prefix_cache (the field
            # the user just added)
            if not self.block_size:
                bad("prefix_cache='on' needs the paged KV layout: set "
                    f"block_size > 0 (got block_size={self.block_size}) "
                    "— prefix sharing is per physical block")
            if self.kv_quant:
                bad("prefix_cache is incompatible with kv_quant (the "
                    "int8 cache is dense-layout, full-admission only)")
            if self.disagg:
                bad("prefix_cache is incompatible with disagg: the "
                    "decode pool admits via KV handoff, not prompts "
                    "(colocated trace serving only)")
            if self.admit_chunk < 1 or self.admit_chunk % self.block_size:
                bad(f"admit_chunk={self.admit_chunk} must be a positive "
                    f"multiple of block_size={self.block_size} for "
                    "prefix_cache (a spliced prefix must end on a chunk "
                    "boundary)")
            if self.s_max % self.admit_chunk:
                bad(f"s_max={self.s_max} must be a multiple of "
                    f"admit_chunk={self.admit_chunk} for prefix_cache "
                    "(hits prefill their suffix through the chunked "
                    "executables)")
            if self.prefix_capacity is not None \
                    and self.prefix_capacity < 1:
                bad(f"prefix_capacity={self.prefix_capacity} must be "
                    ">= 1 (or None for pool-bounded)")
            from ..configs import get_smoke
            if get_smoke(self.arch).family != "dense":
                bad("prefix_cache rides the chunked suffix-prefill path: "
                    "dense (attention-only) families only, not "
                    f"arch={self.arch!r}")
        if self.kv_quant:
            if self.admit_mode == "chunked":
                bad("kv_quant is incompatible with admit_mode='chunked': "
                    "chunked prefill cannot re-read the int8 cache "
                    "mid-prompt (use admit_mode='full')")
            if self.block_size:
                bad("kv_quant is incompatible with block_size > 0 (paged "
                    "KV blocks are not scale-grouped); drop one of the "
                    "two")
            if self.spec_mode:
                bad("kv_quant is incompatible with spec_mode: the verify "
                    "pass rides chunked prefill over the int8 cache")
            if self.disagg:
                bad("kv_quant is incompatible with disagg: the KV "
                    "handoff ships fp states between pools")
        if self.disagg:
            if self.prefill_tp < 1 or self.decode_tp < 1:
                bad(f"prefill_tp={self.prefill_tp}/decode_tp="
                    f"{self.decode_tp} must be >= 1")
            if self.prefill_tp % self.prefill_pods:
                bad(f"prefill_tp={self.prefill_tp} not divisible by "
                    f"prefill_pods={self.prefill_pods}")
            if self.decode_tp % self.decode_pods:
                bad(f"decode_tp={self.decode_tp} not divisible by "
                    f"decode_pods={self.decode_pods}")
            if self.max_handoff_retries < 0 or self.max_reprefills < 0:
                bad("max_handoff_retries/max_reprefills must be >= 0")
        return self

    def replace(self, **kw) -> "ReplicaSpec":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    """A deployment: mode + replica template + fleet shape."""
    replica: ReplicaSpec
    mode: str = "trace"
    replicas: int = 1
    router_policy: str = "round_robin"

    def validate(self) -> "ServeSpec":
        """The single home of combo validation (CLI parse time, the
        factories, and router replica construction all call this)."""
        if self.replicas < 1:
            raise SpecError(f"replicas={self.replicas} must be >= 1")
        if self.router_policy not in ROUTER_POLICIES:
            raise SpecError(f"unknown router_policy="
                            f"{self.router_policy!r} (one of "
                            f"{ROUTER_POLICIES})")
        if self.replicas > 1 and self.mode != "trace":
            raise SpecError("replicas > 1 is trace-mode only (the router "
                            "tier replays a request trace)")
        self.replica.validate(mode=self.mode)
        return self

    def replace(self, **kw) -> "ServeSpec":
        return dataclasses.replace(self, **kw)

    # -- CLI / JSON -------------------------------------------------------

    @classmethod
    def from_args(cls, ns) -> "ServeSpec":
        """Build (and validate) a spec from a ``launch.serve`` argparse
        namespace.  CLI sentinel values are normalized here — the spec
        stores canonical forms (``spec_mode=None``, ``ar_quant='none'``)."""
        spec_mode = None if ns.spec_mode in (None, "none") else ns.spec_mode
        ar_quant = "none" if ns.ar_quant == "off" else ns.ar_quant
        replica = ReplicaSpec(
            arch=ns.arch, smoke=ns.smoke, tp=ns.tp, pods=ns.pods,
            ar_strategy=ns.ar_strategy, ar_table=ns.ar_table,
            overlap=ns.overlap, seq_parallel=ns.seq_parallel,
            ar_quant=ar_quant, slots=ns.slots, s_max=ns.s_max,
            block_size=ns.block_size, n_blocks=ns.n_blocks,
            kv_quant=ns.kv_quant, admit_mode=ns.admit_mode,
            admit_chunk=ns.admit_chunk, prefix_cache=ns.prefix_cache,
            prefix_capacity=ns.prefix_capacity, temperature=ns.temperature,
            top_k=ns.top_k, seed=ns.seed, spec_mode=spec_mode,
            spec_k=ns.spec_k, spec_adaptive=ns.spec_adaptive,
            draft_arch=ns.draft_arch, fault_plan=ns.fault_plan,
            deadline_ms=ns.deadline_ms, disagg=ns.disagg,
            prefill_tp=ns.prefill_tp, prefill_pods=ns.prefill_pods,
            decode_tp=ns.decode_tp, decode_pods=ns.decode_pods,
            prefill_ar_table=ns.prefill_ar_table,
            decode_ar_table=ns.decode_ar_table,
            prefill_per_step=ns.prefill_per_step)
        return cls(replica=replica, mode=ns.mode,
                   replicas=getattr(ns, "replicas", 1),
                   router_policy=getattr(ns, "router_policy",
                                         "round_robin")).validate()

    def to_json(self) -> str:
        d = dataclasses.asdict(self)
        d["replica"] = dataclasses.asdict(self.replica)
        return json.dumps(d, indent=2)

    @classmethod
    def from_json(cls, s: str) -> "ServeSpec":
        """Inverse of :meth:`to_json`; unknown keys are an error (a
        mistyped field silently reverting to a default is exactly the
        config bug specs exist to prevent)."""
        d = json.loads(s)
        if not isinstance(d, dict):
            raise SpecError(f"spec JSON must be an object, got "
                            f"{type(d).__name__}")
        rd = d.pop("replica", None)
        if rd is None:
            raise SpecError("spec JSON is missing the 'replica' object")
        known_r = {f.name for f in dataclasses.fields(ReplicaSpec)}
        unknown = sorted(set(rd) - known_r)
        if unknown:
            raise SpecError(f"unknown ReplicaSpec field(s): {unknown}")
        known_s = {f.name for f in dataclasses.fields(cls)} - {"replica"}
        unknown = sorted(set(d) - known_s)
        if unknown:
            raise SpecError(f"unknown ServeSpec field(s): {unknown}")
        return cls(replica=ReplicaSpec(**rd), **d).validate()


# ---------------------------------------------------------------------------
# factories: the one construction path for every deployment shape
# ---------------------------------------------------------------------------


def _plan(spec: ReplicaSpec, tp: int):
    from ..configs import get_config, get_smoke
    from ..models.transformer import make_plan
    cfg = get_smoke(spec.arch) if spec.smoke else get_config(spec.arch)
    return make_plan(cfg, tp)


def _init_params(spec: ReplicaSpec, ap, ctx, mesh, device=None):
    """Weights from ``PRNGKey(spec.seed)``, created by one jit straight
    into the shardings the replica's steps read them in (no host copy, no
    stacked second copy, no re-layout on the first call)."""
    import jax
    from ..models.transformer import init_params
    from ..parallel.steps import serve_param_shardings
    out = serve_param_shardings(ap, ctx, mesh, fsdp_serve=spec.fsdp_serve,
                                device=device)
    return jax.jit(lambda k: init_params(k, ap), out_shardings=out)(
        jax.random.PRNGKey(spec.seed))


def _local_device(devices, tp: int):
    """The one device of a mesh-less (``tp == 1``) replica's group."""
    return devices[0] if devices is not None and tp == 1 else None


def make_injector(spec: ReplicaSpec, replica_id: int = 0):
    """``spec.fault_plan`` -> :class:`FaultInjector` (None when absent).

    ``replica_id`` is folded into the plan seed so a fleet built from one
    template gets *independent* deterministic fault schedules per replica
    — one replica's drops/stalls never mirror onto another's requests
    (the per-replica fault-isolation contract, tested in
    tests/test_router.py)."""
    if spec.fault_plan is None:
        return None
    from .faults import FaultInjector, FaultPlan
    plan = FaultPlan.parse(spec.fault_plan)
    if replica_id:
        plan = dataclasses.replace(plan, seed=plan.seed + 7919 * replica_id)
    return FaultInjector(plan)


def build_engine(spec: ReplicaSpec, *, ap=None, params=None, drafter=None):
    """``ReplicaSpec`` -> :class:`InferenceEngine` (the batch path)."""
    from .engine import InferenceEngine
    from ..parallel.topology import mesh_and_ctx
    spec.validate(mode="batch")
    mesh, ctx, tp = mesh_and_ctx(
        spec.tp, spec.pods, ar_strategy=spec.ar_strategy,
        overlap=spec.overlap, seq_parallel=spec.seq_parallel,
        ar_quant=spec.ar_quant)
    if ap is None:
        ap = _plan(spec, tp)
    if params is None:
        params = _init_params(spec, ap, ctx, mesh)
    return InferenceEngine(
        ap, params, ctx=ctx, mesh=mesh, s_max=spec.s_max,
        fsdp_serve=spec.fsdp_serve, scan_layers=spec.scan_layers,
        temperature=spec.temperature, top_k=spec.top_k, seed=spec.seed,
        block_size=spec.block_size, ar_table=spec.ar_table,
        spec_mode=spec.spec_mode, spec_k=spec.spec_k,
        draft_arch=spec.draft_arch, drafter=drafter)


def build_prefill_pool(spec: ReplicaSpec, *, ap=None, params=None,
                       ar_table=None, devices=None):
    """``ReplicaSpec`` -> :class:`PrefillPool` on the spec's *prefill*
    layout (``prefill_tp``/``prefill_pods``; ``seq_parallel`` shapes the
    prefill pool only).  ``ar_table`` overrides ``spec.prefill_ar_table``
    (e.g. an already-resolved :func:`pool_tuner`)."""
    from .disagg import PrefillPool, pool_tuner
    from ..parallel.topology import mesh_and_ctx
    spec.validate(mode="trace")
    mesh, ctx, tp = mesh_and_ctx(
        spec.prefill_tp, spec.prefill_pods, ar_strategy=spec.ar_strategy,
        overlap=spec.overlap, seq_parallel=spec.seq_parallel,
        ar_quant=spec.ar_quant,
        devices=None if devices is None else devices[:spec.prefill_tp])
    device = _local_device(devices, tp)
    if ap is None:
        ap = _plan(spec, tp)
    if params is None:
        params = _init_params(spec, ap, ctx, mesh, device)
    if ar_table is None:
        ar_table = pool_tuner(spec.prefill_ar_table or spec.ar_table)
    return PrefillPool(
        ap, params, s_max=spec.s_max, ctx=ctx, mesh=mesh, device=device,
        ar_table=ar_table, temperature=spec.temperature, top_k=spec.top_k,
        seed=spec.seed, scan_layers=spec.scan_layers,
        fsdp_serve=spec.fsdp_serve, admit_mode=spec.admit_mode,
        admit_chunk=spec.admit_chunk,
        block_size=spec.block_size if spec.prefill_block_size is None
        else spec.prefill_block_size)


def _build_batcher(spec: ReplicaSpec, *, ap, params, drafter, injector,
                   devices, ar_table, seq_parallel, deadline):
    from .scheduler import ContinuousBatcher
    from ..parallel.topology import mesh_and_ctx
    mesh, ctx, tp = mesh_and_ctx(
        spec.tp, spec.pods, ar_strategy=spec.ar_strategy,
        overlap=spec.overlap, seq_parallel=seq_parallel,
        ar_quant=spec.ar_quant, devices=devices)
    device = _local_device(devices, tp)
    if ap is None:
        ap = _plan(spec, tp)
    if params is None:
        params = _init_params(spec, ap, ctx, mesh, device)
    return ContinuousBatcher(
        ap, params, slots=spec.slots, s_max=spec.s_max, ctx=ctx, mesh=mesh,
        device=device,
        block_size=spec.block_size, n_blocks=spec.n_blocks,
        kv_quant=spec.kv_quant, ar_table=ar_table,
        temperature=spec.temperature, top_k=spec.top_k, seed=spec.seed,
        scan_layers=spec.scan_layers, fsdp_serve=spec.fsdp_serve,
        admit_mode=spec.admit_mode, admit_chunk=spec.admit_chunk,
        spec_mode=spec.spec_mode, spec_k=spec.spec_k,
        spec_adaptive=spec.spec_adaptive, draft_arch=spec.draft_arch,
        drafter=drafter, injector=injector, deadline_s=deadline,
        spec_autodisable_after=spec.spec_autodisable_after,
        prefix_cache=spec.prefix_cache,
        prefix_capacity=spec.prefix_capacity)


def build_replica(spec: ReplicaSpec, *, ap=None, params=None, drafter=None,
                  injector=None, devices=None, replica_id: int = 0,
                  prefill_ap=None, prefill_params=None,
                  decode_ap=None, decode_params=None):
    """The one replica factory: ``ReplicaSpec`` ->
    :class:`ContinuousBatcher` (colocated) or :class:`DisaggCoordinator`
    (``spec.disagg``).  Validates first, so a router-constructed replica
    rejects exactly like the CLI.

    ``ap``/``params`` short-circuit plan/weight construction (tests and
    fleets share one weight init; params from ``PRNGKey(spec.seed)``
    otherwise, so sharing is the default behavior anyway).  ``devices``
    restricts the replica's mesh(es) to a disjoint device group (see
    ``parallel.topology.replica_device_groups``); a one-device group puts
    a ``tp == 1`` replica's weights and cache on that device.  ``injector`` overrides
    the one :func:`make_injector` derives from ``spec.fault_plan`` +
    ``replica_id``.  ``prefill_ap``/``decode_ap`` (+ ``*_params``) give a
    disagg replica with *heterogeneous* pool TP degrees caller-built
    plans per pool — the dist cases feed both pools one tiny non-registry
    model this way.
    """
    from jax.profiler import TraceAnnotation
    with TraceAnnotation("serve.build"):
        spec.validate(mode="trace")
        if injector is None:
            injector = make_injector(spec, replica_id)
        if not spec.disagg:
            return _build_batcher(
                spec, ap=ap, params=params, drafter=drafter,
                injector=injector, devices=devices, ar_table=spec.ar_table,
                seq_parallel=spec.seq_parallel, deadline=spec.deadline_ms)
        # -- disaggregated replica: prefill pool, decode batcher, coordinator
        from .disagg import DisaggCoordinator, pool_tuner
        tuner_p = pool_tuner(spec.prefill_ar_table or spec.ar_table)
        tuner_d = pool_tuner(spec.decode_ar_table or spec.ar_table)
        # caller-supplied ap/params are honored only when both pools share
        # one TP layout (the common local-test shape); otherwise each pool
        # gets its own plan + params from PRNGKey(spec.seed) — same
        # weights, each pool's layout (the run_disagg contract) — unless
        # the caller passed explicit per-pool plans
        shared = spec.prefill_tp == spec.decode_tp
        if prefill_ap is None:
            prefill_ap = ap if shared else None
            prefill_params = params if shared else None
        pool = build_prefill_pool(
            spec, ap=prefill_ap, params=prefill_params,
            ar_table=tuner_p, devices=devices)
        # the decode pool admits via handoff splice, never from prompts —
        # force full-admission executables, fused (non-SP) residuals
        decode_spec = spec.replace(tp=spec.decode_tp,
                                   pods=spec.decode_pods, admit_mode="full")
        if decode_ap is None:
            decode_ap = ap if shared else None
            decode_params = pool.params if shared else None
        decode = _build_batcher(
            decode_spec, ap=decode_ap, params=decode_params, drafter=drafter,
            injector=injector,
            devices=None if devices is None else devices[:spec.decode_tp],
            ar_table=tuner_d, seq_parallel="off", deadline=None)
        return DisaggCoordinator(
            pool, decode, prefill_per_step=spec.prefill_per_step,
            decode_tuner=tuner_d, injector=injector,
            max_handoff_retries=spec.max_handoff_retries,
            retry_backoff=spec.retry_backoff, max_ready=spec.max_ready,
            max_reprefills=spec.max_reprefills, deadline_s=spec.deadline_ms)


__all__ = ["ReplicaSpec", "ServeSpec", "SpecError", "ROUTER_POLICIES",
           "PREFIX_MODES", "build_replica", "build_engine",
           "build_prefill_pool", "make_injector"]
