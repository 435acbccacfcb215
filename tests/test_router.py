"""Multi-replica router + ServeSpec config API tests.

Four layers of guarantee:

* placement policies are pure functions over :class:`ReplicaLoad`
  snapshots — unit-tested on synthetic queue states with no engine;
* the spec API round-trips (``from_json(to_json()) == spec``), rejects
  unknown keys, and rejects every known-bad field combination with an
  error that names the offending spec fields — identically at CLI parse
  time and in the factories;
* a ``round_robin`` fleet is token-identical per request to N standalone
  replicas each fed its own arrival-index subset (fleet == N independent
  singles), including under a fault plan (per-replica injector seeds);
* ``ServeMetrics``/``DisaggMetrics`` merge losslessly: fleet percentiles
  are recomputed from retained samples, counters are summed.
"""
import json

import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models.transformer import make_plan, init_params
from repro.inference.router import (POLICIES, ReplicaLoad, Router,
                                    place_least_queue, place_round_robin,
                                    place_ttft_aware, prefill_cost_model)
from repro.inference.scheduler import Request, ServeMetrics, make_trace
from repro.inference.spec import (ReplicaSpec, ServeSpec, SpecError,
                                  build_replica, make_injector)

RS = ReplicaSpec(arch="llama3.2-1b", slots=2, s_max=96)


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = get_smoke("llama3.2-1b")
    ap = make_plan(cfg, 1)
    params = init_params(jax.random.PRNGKey(0), ap)
    return cfg, ap, params


def _copy(reqs):
    return [Request(rid=r.rid, prompt=r.prompt.copy(), max_new=r.max_new,
                    arrival_s=r.arrival_s) for r in reqs]


# ---------------------------------------------------------------------------
# placement policies on synthetic load snapshots (no engine)
# ---------------------------------------------------------------------------


def _load(queue=0, active=0, slots=2, est_q=0.0, est_a=0.0, q_tokens=0,
          remaining=0):
    return ReplicaLoad(queue_depth=queue, queued_prompt_tokens=q_tokens,
                       active=active, slots=slots, active_remaining=remaining,
                       est_queue_cost=est_q, est_active_cost=est_a)


def test_round_robin_ignores_load():
    loads = [_load(queue=9), _load(), _load()]
    assert [place_round_robin(loads, rr) for rr in range(5)] == \
        [0, 1, 2, 0, 1]


def test_least_queue_counts_queued_and_active():
    # replica 0: 2 queued; replica 1: 1 queued + 2 active; replica 2 idle
    loads = [_load(queue=2), _load(queue=1, active=2), _load()]
    assert place_least_queue(loads, 0) == 2
    # deterministic tie-break: lowest index
    assert place_least_queue([_load(), _load()], 7) == 0


def test_ttft_aware_prefers_cheapest_queue():
    # replica 0 queues one huge prompt, replica 1 queues three tiny ones:
    # least_queue picks 1's count... ttft_aware picks the cheaper queue 1
    loads = [_load(queue=1, est_q=500.0), _load(queue=3, est_q=30.0)]
    assert place_ttft_aware(loads, 0) == 1
    assert place_least_queue(loads, 0) == 0


def test_ttft_aware_counts_active_drain_only_when_saturated():
    # both queues empty; replica 0 has a free slot, replica 1 is saturated
    # with long decodes -> its drain cost counts and 0 wins
    loads = [_load(active=1, slots=2, est_a=100.0),
             _load(active=2, slots=2, est_a=100.0)]
    assert place_ttft_aware(loads, 0) == 0
    # two idle replicas look identical -> queue-depth tie-break -> index 0
    assert place_ttft_aware([_load(), _load()], 3) == 0


def test_prefill_cost_model_monotone_and_tp_aware():
    c1 = prefill_cost_model(RS)
    # below the chip's GEMM tile floor (128) the compute term is flat at
    # tp=1; past it cost is strictly monotone in the prompt
    assert 0.0 < c1(8) == c1(64) <= c1(128) < c1(512) < c1(2048)
    # with tp > 1 the per-layer AR term scales with the raw message, so
    # cost is strictly monotone even under the tile floor
    c8 = prefill_cost_model(RS.replace(tp=8, pods=2))
    assert 0.0 < c8(8) < c8(64) < c8(512)
    # disagg replicas cost prefill at the *prefill* pool's layout
    cd = prefill_cost_model(RS.replace(disagg=True, prefill_tp=8,
                                       prefill_pods=2, decode_tp=1))
    assert cd(64) == pytest.approx(c8(64))


def test_router_constructor_rejects():
    with pytest.raises(ValueError, match="at least one"):
        Router([])
    with pytest.raises(ValueError, match="unknown router policy"):
        Router([object()], policy="fastest")
    class _Coord:
        decode = None
    with pytest.raises(ValueError, match="heterogeneous"):
        Router([object(), _Coord()])


# ---------------------------------------------------------------------------
# ServeSpec: JSON round-trip, unknown keys, combo validation
# ---------------------------------------------------------------------------


ROUND_TRIP_SPECS = [
    ServeSpec(replica=RS),
    ServeSpec(replica=RS.replace(block_size=8, n_blocks=13, kv_quant=False,
                                 admit_mode="chunked", admit_chunk=16)),
    ServeSpec(replica=RS.replace(tp=8, pods=2, ar_strategy="auto",
                                 overlap=True, seq_parallel="auto",
                                 ar_quant="auto")),
    ServeSpec(replica=RS.replace(spec_mode="ngram", spec_k=6,
                                 spec_adaptive=True)),
    ServeSpec(replica=RS.replace(fault_plan="nan_logits=0.1,seed=3",
                                 deadline_ms=12.0)),
    ServeSpec(replica=RS.replace(disagg=True, prefill_tp=2, decode_tp=2,
                                 prefill_block_size=0, block_size=8,
                                 max_ready=3, prefill_per_step=4)),
    ServeSpec(replica=RS, replicas=4, router_policy="ttft_aware"),
    ServeSpec(replica=RS.replace(temperature=1.5, top_k=20, seed=9),
              mode="batch"),
]


@pytest.mark.parametrize("spec", ROUND_TRIP_SPECS,
                         ids=lambda s: f"{s.mode}-r{s.replicas}")
def test_spec_json_round_trip(spec):
    assert ServeSpec.from_json(spec.to_json()) == spec


def test_spec_json_rejects_unknown_keys():
    d = json.loads(ServeSpec(replica=RS).to_json())
    d["replica"]["blok_size"] = 8          # typo'd replica field
    with pytest.raises(SpecError, match="blok_size"):
        ServeSpec.from_json(json.dumps(d))
    d = json.loads(ServeSpec(replica=RS).to_json())
    d["router_polcy"] = "round_robin"      # typo'd deployment field
    with pytest.raises(SpecError, match="router_polcy"):
        ServeSpec.from_json(json.dumps(d))
    with pytest.raises(SpecError, match="replica"):
        ServeSpec.from_json("{}")
    with pytest.raises(SpecError, match="object"):
        ServeSpec.from_json("[1, 2]")


# (replica replace kwargs, mode, fragment the error must name)
BAD_COMBOS = [
    (dict(arch="llama-999t"), "trace", "arch"),
    (dict(ar_strategy="warp"), "trace", "ar_strategy"),
    (dict(seq_parallel="maybe"), "trace", "seq_parallel"),
    (dict(ar_quant="int2"), "trace", "ar_quant"),
    (dict(admit_mode="eager"), "trace", "admit_mode"),
    (dict(spec_mode="psychic"), "trace", "spec_mode"),
    (dict(slots=0), "trace", "slots"),
    (dict(tp=0), "trace", "tp"),
    (dict(tp=6, pods=4), "trace", "divisible"),
    (dict(admit_mode="chunked", admit_chunk=28), "trace", "admit_chunk"),
    (dict(spec_mode="ngram", spec_k=0), "trace", "spec_k"),
    (dict(ar_quant="auto"), "trace", "ar_strategy"),
    (dict(kv_quant=True, admit_mode="chunked"), "trace", "chunked"),
    (dict(kv_quant=True, block_size=8), "trace", "block_size"),
    (dict(kv_quant=True, spec_mode="ngram"), "trace", "spec_mode"),
    (dict(kv_quant=True, disagg=True), "trace", "disagg"),
    (dict(spec_adaptive=True), "batch", "trace-mode only"),
    (dict(fault_plan="oom=0.1"), "batch", "trace-mode only"),
    (dict(deadline_ms=5.0), "batch", "trace-mode only"),
    (dict(disagg=True), "batch", "trace-mode only"),
    (dict(kv_quant=True), "batch", "trace-mode only"),
    (dict(block_size=8, tp=8), "batch", "local-path"),
    (dict(prefix_cache="maybe"), "trace", "prefix_cache"),
    (dict(prefix_cache="on"), "trace", "paged"),
    (dict(prefix_cache="on", block_size=8, kv_quant=True), "trace",
     "prefix_cache is incompatible with kv_quant"),
    (dict(prefix_cache="on", block_size=8, disagg=True), "trace",
     "prefix_cache is incompatible with disagg"),
    (dict(prefix_cache="on", block_size=8, admit_chunk=12), "trace",
     "prefix_cache"),
    (dict(prefix_cache="on", block_size=8, admit_chunk=0), "trace",
     "prefix_cache"),
    (dict(prefix_cache="on", block_size=8, prefix_capacity=0), "trace",
     "prefix_capacity"),
    (dict(prefix_cache="on", block_size=8, arch="hymba-1.5b"), "trace",
     "dense"),
    (dict(prefix_cache="on", block_size=8), "batch", "trace-mode only"),
    (dict(disagg=True, prefill_tp=0), "trace", "prefill_tp"),
    (dict(disagg=True, prefill_tp=6, prefill_pods=4), "trace", "divisible"),
    (dict(disagg=True, decode_tp=6, decode_pods=4), "trace", "divisible"),
    (dict(disagg=True, max_reprefills=-1), "trace", "max_reprefills"),
]


@pytest.mark.parametrize("kw,mode,frag", BAD_COMBOS,
                         ids=[f"{sorted(kw)[0]}-{m}" for kw, m, _ in
                              BAD_COMBOS])
def test_validate_rejects_bad_combos(kw, mode, frag):
    with pytest.raises(SpecError, match=frag):
        RS.replace(**kw).validate(mode=mode)
    # the deployment-level validate rejects identically
    with pytest.raises(SpecError, match=frag):
        ServeSpec(replica=RS.replace(**kw), mode=mode).validate()


def test_deployment_validate_rejects():
    with pytest.raises(SpecError, match="replicas"):
        ServeSpec(replica=RS, replicas=0).validate()
    with pytest.raises(SpecError, match="router_policy"):
        ServeSpec(replica=RS, router_policy="fastest").validate()
    with pytest.raises(SpecError, match="trace-mode only"):
        ServeSpec(replica=RS, replicas=2, mode="batch").validate()


def test_cli_rejects_like_validate():
    """The CLI is a thin shell over ServeSpec.from_args -> validate: a
    bad combo exits with the same field-naming message."""
    from repro.launch.serve import build_parser, main
    base = ["--arch", "llama3.2-1b", "--smoke"]
    for argv, frag in (
            (["--mode", "batch", "--fault-plan", "oom=0.1"],
             "trace-mode only"),
            (["--mode", "trace", "--kv-quant", "--block-size", "8"],
             "block_size"),
            (["--mode", "trace", "--ar-quant", "auto"], "ar_strategy"),
            (["--mode", "trace", "--prefix-cache", "on"], "paged"),
            (["--mode", "trace", "--prefix-cache", "on", "--block-size",
              "8", "--kv-quant"], "prefix_cache"),
            (["--mode", "trace", "--prefix-cache", "on", "--block-size",
              "8", "--arch", "hymba-1.5b"], "dense"),
            (["--mode", "trace", "--admit-mode", "chunked", "--s-max",
              "100", "--admit-chunk", "32"], "admit_chunk")):
        with pytest.raises(SystemExit, match=frag):
            main(base + argv)
    # every parseable combination round-trips through JSON (main asserts
    # this on each invocation; spot-check the parser defaults here)
    ns = build_parser().parse_args(base)
    spec = ServeSpec.from_args(ns)
    assert ServeSpec.from_json(spec.to_json()) == spec


def test_build_replica_validates_first():
    with pytest.raises(SpecError, match="admit_chunk"):
        build_replica(RS.replace(admit_mode="chunked", admit_chunk=28))


def test_make_injector_decorrelates_replicas():
    spec = RS.replace(fault_plan="nan_logits=0.2,seed=3")
    inj0, inj1 = make_injector(spec, 0), make_injector(spec, 1)
    assert inj0.plan.seed == 3
    assert inj1.plan.seed == 3 + 7919
    assert make_injector(RS, 1) is None    # no plan -> no injector


# ---------------------------------------------------------------------------
# fleet == N independent singles (token parity), policies end-to-end
# ---------------------------------------------------------------------------


def _fleet_parity(ap, params, vocab, *, fault_plan=None):
    spec = RS if fault_plan is None else RS.replace(fault_plan=fault_plan)
    reqs = make_trace(8, mean_in=10, mean_out=6, rate=4.0, vocab=vocab,
                      seed=2)
    fleet = Router([build_replica(spec, ap=ap, params=params, replica_id=i)
                    for i in range(2)], policy="round_robin")
    done = fleet.run(_copy(reqs))
    by_arrival = sorted(reqs, key=lambda r: r.arrival_s)
    for i in range(2):
        solo = build_replica(spec, ap=ap, params=params, replica_id=i)
        sub = _copy([r for k, r in enumerate(by_arrival) if k % 2 == i])
        solo_done = {r.rid: r for r in solo.run(sub)}
        routed = [r for r in done if r.rid in solo_done]
        assert len(routed) == len(sub)
        for r in routed:
            s = solo_done[r.rid]
            if s.output is None:
                assert r.output is None and r.shed_reason == s.shed_reason
            else:
                np.testing.assert_array_equal(r.output, s.output)
    return fleet, done


def test_fleet_round_robin_token_parity(tiny_lm):
    cfg, ap, params = tiny_lm
    fleet, done = _fleet_parity(ap, params, cfg.vocab_size)
    assert fleet.placements == [4, 4]
    m = fleet.metrics(done)
    assert m.fleet.completed == 8
    assert m.load_imbalance == 1.0
    assert [p.completed for p in m.per_replica] == [4, 4]
    d = m.to_dict()
    assert d["policy"] == "round_robin" and d["replicas"] == 2


def test_fleet_fault_isolation_parity(tiny_lm):
    """Fleet under a fault plan == standalone replicas with the same
    per-replica derived injectors: one replica's deterministic fault
    schedule never leaks onto another's requests."""
    cfg, ap, params = tiny_lm
    _fleet_parity(ap, params, cfg.vocab_size,
                  fault_plan="nan_logits=0.3,seed=5")


def test_one_device_replicas_need_a_device_each(tiny_lm):
    """tp=1 replicas are carved a device each, like tp>1 ones: too few
    devices is an error, and stacking them on one device is spelled out."""
    from repro.parallel.topology import replica_device_groups
    dev = jax.devices()[0]
    with pytest.raises(ValueError, match="needs 2 devices"):
        replica_device_groups(2, 1, [dev])
    assert replica_device_groups(2, 1, [dev, dev]) == [[dev], [dev]]
    cfg, ap, params = tiny_lm
    fleet = Router.from_spec(ServeSpec(replica=RS, replicas=2), ap=ap,
                             params=params, devices=[dev, dev])
    for rep in fleet.replicas:
        assert {d for leaf in jax.tree.leaves((rep.params, rep.cache))
                for d in leaf.devices()} == {dev}
    reqs = make_trace(4, mean_in=10, mean_out=4, rate=4.0,
                      vocab=cfg.vocab_size, seed=1)
    assert fleet.metrics(fleet.run(reqs)).fleet.completed == 4


def test_policies_complete_bursty_trace(tiny_lm):
    cfg, ap, params = tiny_lm
    reqs = make_trace(10, mean_in=10, mean_out=6, rate=8.0,
                      vocab=cfg.vocab_size, seed=3)
    for policy in ("least_queue", "ttft_aware"):
        fleet = Router([build_replica(RS, ap=ap, params=params)
                        for _ in range(2)], policy=policy,
                       cost_fn=prefill_cost_model(RS))
        done = fleet.run(_copy(reqs))
        m = fleet.metrics(done)
        assert m.fleet.completed == len(reqs), policy
        assert all(p > 0 for p in fleet.placements), \
            f"{policy}: a replica got no traffic {fleet.placements}"


def test_ttft_aware_beats_round_robin_on_skewed_bursts(tiny_lm):
    """Three bursts of 8 requests in which heavy (112-token prompt, 24
    new tokens) and light (8, 4) requests alternate by arrival index, so
    ``round_robin`` over 2 replicas lands every heavy request on replica
    0.  ``ttft_aware`` estimates each replica's wait to a first token and
    steers away from it: its p99 TTFT is strictly lower and its goodput
    (new tokens per logical step) no lower."""
    cfg, ap, params = tiny_lm
    spec = ReplicaSpec(arch="llama3.2-1b", slots=2, s_max=160, block_size=8,
                       admit_mode="chunked", admit_chunk=16)
    rng = np.random.default_rng(11)
    reqs = []
    for b in range(3):
        for k in range(8):
            heavy = k % 2 == 0
            reqs.append(Request(
                rid=len(reqs), prompt=rng.integers(
                    0, cfg.vocab_size, 112 if heavy else 8).astype(np.int32),
                max_new=24 if heavy else 4, arrival_s=0.8 * b))
    got = {}
    for policy in ("round_robin", "ttft_aware"):
        fleet = Router([build_replica(spec, ap=ap, params=params,
                                      replica_id=i) for i in range(2)],
                       policy=policy, cost_fn=prefill_cost_model(spec))
        m = fleet.metrics(fleet.run(_copy(reqs))).fleet
        assert m.completed == len(reqs), policy
        assert all(p > 0 for p in fleet.placements), policy
        got[policy] = m
    rr, ta = got["round_robin"], got["ttft_aware"]
    assert ta.ttft_steps_p99 < rr.ttft_steps_p99, \
        (ta.ttft_steps_p99, rr.ttft_steps_p99)
    assert ta.total_new_tokens / max(ta.steps, 1) \
        >= rr.total_new_tokens / max(rr.steps, 1)


# ---------------------------------------------------------------------------
# lossless metrics merge
# ---------------------------------------------------------------------------


def test_serve_metrics_merge_lossless(tiny_lm):
    cfg, ap, params = tiny_lm
    parts = []
    for seed in (2, 3):
        sched = build_replica(RS, ap=ap, params=params)
        done = sched.run(make_trace(5, mean_in=10, mean_out=6, rate=3.0,
                                    vocab=cfg.vocab_size, seed=seed))
        parts.append(sched.metrics(done))
    fleet = ServeMetrics.merge(parts)
    ttft = [s for m in parts for s in m.ttft_steps_samples]
    tpot = [s for m in parts for s in m.tpot_steps_samples]
    assert len(ttft) == 10
    assert fleet.completed == sum(m.completed for m in parts) == 10
    assert fleet.total_new_tokens == sum(m.total_new_tokens for m in parts)
    # exact percentiles over the pooled samples — not averaged p99s
    assert fleet.ttft_steps_p99 == pytest.approx(
        float(np.percentile(np.asarray(ttft, np.float64), 99)))
    assert fleet.tpot_steps_p50 == pytest.approx(
        float(np.percentile(np.asarray(tpot, np.float64), 50)))
    # merge keeps the samples, so a merge of merges is still lossless
    again = ServeMetrics.merge([fleet])
    assert again.ttft_steps_p99 == fleet.ttft_steps_p99
    assert sorted(again.ttft_steps_samples) == sorted(ttft)
    # samples never leak into bench JSON rows
    assert "ttft_steps_samples" not in fleet.to_dict()
    with pytest.raises(ValueError):
        ServeMetrics.merge([])
