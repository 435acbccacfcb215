"""Fault-injected serving (DESIGN.md §11, docs/robustness.md).

The contract under test: every injected fault schedule is deterministic
(a pure hash of seed/kind/ids), every non-shed greedy request's tokens
are bitwise-identical to the fault-free trace, shed requests are always
reported, and every recovery ladder (retry -> re-prefill -> shed,
quarantine -> recompute, OOM -> evict -> recompute) terminates.
"""
import json

import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.autotune import AutoTuner, TABLE_VERSION
from repro.inference.faults import (FAULT_KINDS, FaultInjector, FaultPlan,
                                    hash_unit)
from repro.inference.kv_cache import BundleIntegrityError, KVBundle
from repro.inference.scheduler import make_trace
from repro.inference.spec import ReplicaSpec, build_replica
from repro.inference.speculative import Drafter

# spec templates: paged colocated batcher / disagg with a dense prefill
# pool in front of the paged decode pool (the historical test shape)
RS = ReplicaSpec(arch="llama3.2-1b", slots=3, s_max=96, block_size=8)
DS = RS.replace(disagg=True, prefill_block_size=0)


@pytest.fixture(scope="module")
def tiny_lm():
    from repro.models.transformer import make_plan, init_params
    cfg = get_smoke("llama3.2-1b")
    ap = make_plan(cfg, 1)
    params = init_params(jax.random.PRNGKey(0), ap)
    return cfg, ap, params


def _trace(cfg, n=10, seed=4, mean_in=10, mean_out=6, rate=3.0):
    return make_trace(n, mean_in=mean_in, mean_out=mean_out, rate=rate,
                      vocab=cfg.vocab_size, seed=seed)


def _colocated(ap, params, reqs, injector=None, drafter=None, **kw):
    sched = build_replica(RS.replace(**kw), ap=ap, params=params,
                          injector=injector, drafter=drafter)
    done = sched.run(reqs)
    return {r.rid: r.output for r in done}, sched


def _disagg(ap, params, reqs, injector=None, **kw):
    # one injector drives the coordinator's handoff hooks AND the decode
    # batcher's step hooks (the build_replica contract)
    coord = build_replica(DS.replace(**kw), ap=ap, params=params,
                          injector=injector)
    done = coord.run(reqs)
    return {r.rid: r.output for r in done}, coord


# ---------------------------------------------------------------------------
# fault plan / injector determinism
# ---------------------------------------------------------------------------


def test_hash_unit_deterministic_and_uniform_ish():
    a = hash_unit(7, "handoff_drop", 3, 1)
    assert a == hash_unit(7, "handoff_drop", 3, 1)
    assert 0.0 <= a < 1.0
    # different ids / kinds / seeds decorrelate
    draws = {hash_unit(s, k, i) for s in (0, 1) for i in range(8)
             for k in ("handoff_drop", "nan_logits")}
    assert len(draws) == 32


def test_fault_events_nest_across_rates():
    """The event set at rate r1 is a subset of the set at r2 >= r1 —
    the property the bench's goodput monotonicity stands on."""
    lo = FaultInjector(FaultPlan(seed=3, handoff_drop=0.1))
    hi = FaultInjector(FaultPlan(seed=3, handoff_drop=0.4))
    fired_lo = {(r, a) for r in range(20) for a in range(4)
                if lo.drop_handoff(r, a)}
    fired_hi = {(r, a) for r in range(20) for a in range(4)
                if hi.drop_handoff(r, a)}
    assert fired_lo and fired_lo < fired_hi
    assert lo.counts["handoff_drop"] == len(fired_lo)


def test_nan_events_fire_once_per_progress_key():
    """A quarantined request replays the same (rid, progress) keys; the
    injector must not re-poison it into a livelock."""
    inj = FaultInjector(FaultPlan(seed=0, nan_logits=0.5))
    first = [inj.poison_slot(5, e) for e in range(10)]
    again = [inj.poison_slot(5, e) for e in range(10)]
    assert any(first) and not any(again)
    inj.reset_stats()   # a reset replays the same schedule
    assert [inj.poison_slot(5, e) for e in range(10)] == first


def test_fault_plan_parse_string_json_and_errors(tmp_path):
    p = FaultPlan.parse("seed=9, handoff_drop=0.25,stall_steps=5")
    assert (p.seed, p.handoff_drop, p.stall_steps) == (9, 0.25, 5)
    doc = tmp_path / "plan.json"
    doc.write_text(json.dumps({"seed": 2, "nan_logits": 0.1}))
    p2 = FaultPlan.parse(str(doc))
    assert (p2.seed, p2.nan_logits) == (2, 0.1)
    assert p2.any_faults and not FaultPlan().any_faults
    with pytest.raises(ValueError, match="unknown fault-plan key"):
        FaultPlan.parse("bogus=1")
    with pytest.raises(ValueError, match="outside"):
        FaultPlan.parse("handoff_drop=1.5")
    with pytest.raises(ValueError, match="key=value"):
        FaultPlan.parse("seed")
    assert set(FaultInjector(p).stats()) == set(FAULT_KINDS)


# ---------------------------------------------------------------------------
# KV bundle integrity
# ---------------------------------------------------------------------------


def test_bundle_checksum_detects_corruption():
    rng = np.random.default_rng(0)
    k = rng.standard_normal((2, 6, 2, 8)).astype(np.float32)
    b = KVBundle(k=k.copy(), v=k.copy() + 1).seal()
    b.verify()                       # sealed + intact: fine
    KVBundle(k=k.copy(), v=k.copy()).verify()   # unsealed: no-op
    FaultInjector.corrupt_bundle(b)  # silent bit damage, not NaN
    assert np.isfinite(b.k).all()
    with pytest.raises(BundleIntegrityError, match="checksum"):
        b.verify()
    # shape/dtype are part of the digest too
    b2 = KVBundle(k=k.copy(), v=k.copy()).seal()
    b2.k = b2.k.reshape(2, 6, 8, 2)
    b2.v = b2.v.reshape(2, 6, 8, 2)
    with pytest.raises(BundleIntegrityError):
        b2.verify()


# ---------------------------------------------------------------------------
# autotuner load hardening (satellite: degrade, never raise mid-trace)
# ---------------------------------------------------------------------------


def test_autotuner_load_degrades_on_corrupt_file(tmp_path):
    for payload in ("{not json", "", "[1, 2]", '"str"'):
        f = tmp_path / "t.json"
        f.write_text(payload)
        with pytest.warns(RuntimeWarning, match="degrading to analytic"):
            t = AutoTuner.load(str(f))
        assert t.table == {} and t.choose(1 << 20, 8, 2) is not None
    with pytest.warns(RuntimeWarning, match="degrading to analytic"):
        AutoTuner.load(str(tmp_path / "missing.json"))


def test_autotuner_load_degrades_on_stale_version(tmp_path):
    t = AutoTuner()
    t.choose(1 << 20, 8, 2)
    doc = t.to_json()
    doc["version"] = TABLE_VERSION + 1
    f = tmp_path / "stale.json"
    f.write_text(json.dumps(doc))
    with pytest.warns(RuntimeWarning, match="schema version"):
        assert AutoTuner.load(str(f)).table == {}


def test_autotuner_valid_roundtrip_and_bad_entry_drop(tmp_path):
    t = AutoTuner()
    t.choose(1 << 20, 8, 2)
    t.choose(1 << 12, 4, 1)
    f = tmp_path / "ok.json"
    t.save(str(f))
    t2 = AutoTuner.load(str(f))   # clean file: no warning, table kept
    assert {k: v.strategy for k, v in t2.table.items()} \
        == {k: v.strategy for k, v in t.table.items()}
    doc = t.to_json()
    good_key = next(iter(doc["table"]))
    doc["table"]["garbage key"] = doc["table"][good_key]
    doc["table"]["b16/f8/s2/bfloat16"] = {"strategy": "warp_drive",
                                          "rd_chunks": 1}
    doc["sp_table"]["nonsense"] = True
    f2 = tmp_path / "mixed.json"
    f2.write_text(json.dumps(doc))
    with pytest.warns(RuntimeWarning, match="dropped 3"):
        t3 = AutoTuner.load(str(f2))
    assert set(t3.table) == set(t.table)   # the good entries survive


# ---------------------------------------------------------------------------
# handoff drop / corruption: retry -> re-prefill -> shed ladder
# ---------------------------------------------------------------------------


def test_handoff_drops_retry_and_stay_bitwise_exact(tiny_lm):
    cfg, ap, params = tiny_lm
    ref, _ = _colocated(ap, params, _trace(cfg))
    inj = FaultInjector(FaultPlan(seed=11, handoff_drop=0.3))
    got, coord = _disagg(ap, params, _trace(cfg), injector=inj)
    assert coord.handoff_drops > 0 and coord.handoff_retries > 0
    for rid in ref:
        np.testing.assert_array_equal(ref[rid], got[rid])


def test_corrupt_handoffs_reprefill_and_stay_bitwise_exact(tiny_lm):
    """Half of all prefills produce corrupt bundles: every corruption is
    *detected* (checksum) and re-prefilled; a request whose re-prefill
    budget runs out is shed with a reason, and every survivor is
    bitwise-exact."""
    cfg, ap, params = tiny_lm
    ref, _ = _colocated(ap, params, _trace(cfg))
    inj = FaultInjector(FaultPlan(seed=5, handoff_corrupt=0.5))
    reqs = _trace(cfg)
    got, coord = _disagg(ap, params, reqs, injector=inj)
    m = coord.metrics(reqs)
    assert coord.handoff_corrupt > 0 and coord.handoff_reprefills > 0
    assert m.handoff_corrupt == coord.handoff_corrupt
    assert m.completed + m.shed_requests == len(reqs)
    for r in reqs:
        if r.output is None:
            assert r.shed_reason == "handoff_corrupt"
        else:
            np.testing.assert_array_equal(ref[r.rid], r.output)


def test_total_handoff_failure_sheds_everything_and_terminates(tiny_lm):
    """handoff_drop=1.0: every transfer attempt dies.  The run must still
    terminate (bounded retries, bounded re-prefills) and every request
    must be shed with a reason — never silently dropped."""
    cfg, ap, params = tiny_lm
    reqs = _trace(cfg, n=6)
    inj = FaultInjector(FaultPlan(seed=1, handoff_drop=1.0))
    got, coord = _disagg(ap, params, reqs, injector=inj,
                         max_handoff_retries=2, max_reprefills=1)
    assert all(v is None for v in got.values())
    assert all(r.shed_reason == "handoff_failed" for r in reqs)
    assert all(r.shed_step >= 0 for r in reqs)
    m = coord.metrics(reqs)
    assert m.shed_requests == len(reqs) and m.completed == 0
    # bounded ladder: per prefill, at most (retries+1) transfer attempts
    assert coord.handoff_drops \
        <= len(reqs) * (coord.max_reprefills + 1) \
        * (coord.max_handoff_retries + 1)


# ---------------------------------------------------------------------------
# bounded handoff queue + stalls (satellite: backpressure, not unbounded RAM)
# ---------------------------------------------------------------------------


def test_decode_stall_backpressures_bounded_ready_queue(tiny_lm):
    """With the decode pool stalling and a tiny ready cap, the prefill
    pool must hold prompts (backpressure) instead of growing the handoff
    queue without bound — and the run still completes bitwise-exact."""
    cfg, ap, params = tiny_lm
    ref, _ = _colocated(ap, params, _trace(cfg))
    inj = FaultInjector(FaultPlan(seed=2, decode_stall=0.4, stall_steps=2))
    got, coord = _disagg(ap, params, _trace(cfg), injector=inj,
                         max_ready=3, prefill_per_step=4)
    m = coord.metrics(list(_trace(cfg)))
    assert m.decode_stall_steps > 0
    assert m.backpressure_steps > 0
    assert m.peak_ready_depth <= 3 and m.ready_cap == 3
    for rid in ref:
        np.testing.assert_array_equal(ref[rid], got[rid])


def test_prefill_stall_only_delays_never_corrupts(tiny_lm):
    cfg, ap, params = tiny_lm
    ref, _ = _colocated(ap, params, _trace(cfg))
    inj = FaultInjector(FaultPlan(seed=6, prefill_stall=0.5, stall_steps=3))
    got, coord = _disagg(ap, params, _trace(cfg), injector=inj)
    assert coord.prefill_stall_steps > 0
    for rid in ref:
        np.testing.assert_array_equal(ref[rid], got[rid])


# ---------------------------------------------------------------------------
# deadline shedding
# ---------------------------------------------------------------------------


def test_deadline_shed_reports_and_preserves_survivors(tiny_lm):
    """A tight TTFT deadline with a stalling prefill pool sheds some
    requests; survivors stay bitwise-exact and shed + completed covers
    the whole trace (nothing silently lost)."""
    cfg, ap, params = tiny_lm
    ref, _ = _colocated(ap, params, _trace(cfg))
    inj = FaultInjector(FaultPlan(seed=3, prefill_stall=0.6, stall_steps=4))
    reqs = _trace(cfg)
    got, coord = _disagg(ap, params, reqs, injector=inj, deadline_ms=4.0)
    m = coord.metrics(reqs)
    assert m.shed_requests > 0, "deadline never tripped — not a test"
    assert m.shed_requests + m.completed == len(reqs)
    for r in reqs:
        if r.output is None:
            assert r.shed_reason == "deadline" and r.shed_step >= 0
        else:
            np.testing.assert_array_equal(ref[r.rid], r.output)


def test_colocated_deadline_shed(tiny_lm):
    """The colocated batcher honors per-run deadlines too: with one slot
    and bursty arrivals, late-queue requests are shed, and the rest are
    bitwise-identical to the no-deadline run."""
    cfg, ap, params = tiny_lm
    reqs_ref = _trace(cfg, n=8, rate=10.0)
    sched = build_replica(RS.replace(slots=1), ap=ap, params=params)
    ref = {r.rid: r.output for r in sched.run(reqs_ref)}
    reqs = _trace(cfg, n=8, rate=10.0)
    tight = build_replica(RS.replace(slots=1, deadline_ms=10.0),
                          ap=ap, params=params)
    done = tight.run(reqs)
    m = tight.metrics(done)
    assert m.shed_requests > 0
    assert m.shed_requests + m.completed == len(reqs)
    for r in reqs:
        if r.output is not None:
            np.testing.assert_array_equal(ref[r.rid], r.output)
        else:
            assert r.shed_reason == "deadline"


# ---------------------------------------------------------------------------
# goodput under a rising fault rate (disagg pool pair)
# ---------------------------------------------------------------------------


def test_goodput_monotone_in_fault_rate(tiny_lm):
    """One mixed fault plan over a rate ladder on a decode-heavy trace
    (short prompts, long outputs: the shape whose decoded tokens a
    quarantine wastes).  Fault events nest across rates (a higher rate
    fires a superset of a lower rate's events), so the useful-work
    fraction ``new / (new + wasted)`` never rises with the rate, and stays
    above zero at 10 %.  At every rate each request completes or is shed
    with a reason, and each survivor matches the fault-free colocated
    tokens."""
    cfg, ap, params = tiny_lm
    geo = dict(slots=4, s_max=128)
    shape = dict(n=12, seed=1, mean_in=8, mean_out=24, rate=2.0)
    reqs = _trace(cfg, **shape)
    assert max(r.prompt.shape[0] for r in reqs) + 1 <= geo["s_max"]
    ref, _ = _colocated(ap, params, reqs, **geo)
    assert all(v is not None for v in ref.values())
    rates = (0.0, 0.05, 0.1, 0.2)
    goodput = []
    for rate in rates:
        plan = FaultPlan(seed=7, handoff_drop=rate, handoff_corrupt=rate / 2,
                         prefill_stall=rate / 2, nan_logits=rate / 5)
        reqs = _trace(cfg, **shape)
        _, coord = _disagg(ap, params, reqs, injector=FaultInjector(plan)
                             if rate else None, **geo)
        m = coord.metrics(reqs)
        assert m.completed + m.shed_requests == len(reqs)
        for r in reqs:
            if r.output is None:
                assert r.shed_reason, f"rid {r.rid} lost without a reason"
            else:
                np.testing.assert_array_equal(ref[r.rid], r.output)
        wasted = m.decode_pool["wasted_tokens"]
        goodput.append(m.total_new_tokens
                       / max(m.total_new_tokens + wasted, 1))
    assert goodput[-1] < 1.0, "no work was wasted: not a test"
    assert all(hi >= lo - 1e-9 for hi, lo in zip(goodput, goodput[1:])), \
        goodput
    assert goodput[rates.index(0.1)] > 0.0, goodput


# ---------------------------------------------------------------------------
# NaN quarantine + OOM bursts (colocated decode path)
# ---------------------------------------------------------------------------


def test_nan_quarantine_recomputes_bitwise_exact(tiny_lm):
    """Injected non-finite KV must be caught by the device-side finite
    guard, the slot quarantined, and the recompute must reproduce the
    fault-free stream exactly — with no NaN left behind in the cache to
    re-poison later occupants of the freed blocks."""
    cfg, ap, params = tiny_lm
    ref, _ = _colocated(ap, params, _trace(cfg))
    inj = FaultInjector(FaultPlan(seed=7, nan_logits=0.08))
    got, sched = _colocated(ap, params, _trace(cfg), injector=inj)
    m = sched.metrics(list(_trace(cfg)))
    assert m.quarantines > 0, "no quarantine fired — not a test"
    assert m.quarantines == inj.counts["nan_logits"], \
        "quarantine storm: one injection must cost exactly one quarantine"
    assert m.wasted_tokens > 0
    for rid in ref:
        np.testing.assert_array_equal(ref[rid], got[rid])
    assert np.isfinite(
        np.asarray(sched.cache["k"], np.float32)).all(), \
        "scrub-on-quarantine left NaN in freed blocks"


def test_injected_oom_bursts_evict_and_recompute(tiny_lm):
    """An OOM burst only bites when a slot actually needs new blocks, so
    run long generations (many growth events) under a high burst rate:
    growing slots are evicted, recomputed, and stay bitwise-exact."""
    cfg, ap, params = tiny_lm
    ref, _ = _colocated(ap, params, _trace(cfg, mean_out=14))
    inj = FaultInjector(FaultPlan(seed=9, oom=0.5))
    reqs = _trace(cfg, mean_out=14)
    got, sched = _colocated(ap, params, reqs, injector=inj)
    m = sched.metrics(reqs)
    assert m.injected_oom > 0, "no burst hit a growth event — not a test"
    assert m.completed == len(reqs)
    for rid in ref:
        np.testing.assert_array_equal(ref[rid], got[rid])


def test_straggler_delays_never_change_tokens(tiny_lm):
    cfg, ap, params = tiny_lm
    ref, _ = _colocated(ap, params, _trace(cfg))
    inj = FaultInjector(FaultPlan(seed=4, straggler=0.3, straggler_s=0.0))
    got, sched = _colocated(ap, params, _trace(cfg), injector=inj)
    m = sched.metrics(list(_trace(cfg)))
    assert m.straggler_steps > 0   # latency noise only, tokens untouched
    for rid in ref:
        np.testing.assert_array_equal(ref[rid], got[rid])


# ---------------------------------------------------------------------------
# speculative decoding degraded mode
# ---------------------------------------------------------------------------


def test_spec_verify_fault_autodisables_slot_and_stays_exact(tiny_lm):
    """A NaN fault under spec decode quarantines the slot AND permanently
    degrades that request to correction-token-only decode; the emitted
    stream still equals plain fault-free decode."""
    cfg, ap, params = tiny_lm
    ref, _ = _colocated(ap, params, _trace(cfg, mean_out=10))
    inj = FaultInjector(FaultPlan(seed=8, nan_logits=0.1))
    got, sched = _colocated(ap, params, _trace(cfg, mean_out=10),
                            injector=inj, spec_mode="ngram", spec_k=3)
    m = sched.metrics(list(_trace(cfg, mean_out=10)))
    assert m.quarantines > 0
    assert m.spec_autodisables > 0
    for rid in ref:
        np.testing.assert_array_equal(ref[rid], got[rid])


class _AlwaysWrongDrafter(Drafter):
    """Proposes tokens the greedy target will (almost) never emit."""

    def _propose(self, slot, hist, k):
        return [(hist[-1] + 17 + i) % 50 for i in range(k)]


def test_spec_acceptance_collapse_autodisable(tiny_lm):
    """A pathologically bad drafter trips the zero-accept-streak breaker
    (spec_autodisable_after) and the run degrades to exact plain decode
    instead of burning k+1-wide verify passes forever."""
    cfg, ap, params = tiny_lm
    ref, _ = _colocated(ap, params, _trace(cfg, mean_out=12))
    got, sched = _colocated(ap, params, _trace(cfg, mean_out=12),
                            spec_mode="ngram", spec_k=3,
                            drafter=_AlwaysWrongDrafter(),
                            spec_autodisable_after=2)
    m = sched.metrics(list(_trace(cfg, mean_out=12)))
    assert m.spec_autodisables > 0
    for rid in ref:
        np.testing.assert_array_equal(ref[rid], got[rid])


# ---------------------------------------------------------------------------
# preemption fairness under overcommit (satellite: randomized)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_overcommitted_pool_completes_and_replays_identically(tiny_lm,
                                                              seed):
    """Randomized overcommit: a paged pool too small for the offered load
    must still finish every request within a bounded step budget (the
    preemption ladder is fair — no livelock), and a replay of the same
    trace must reproduce identical outputs AND identical preemption
    counts (scheduling itself is deterministic)."""
    cfg, ap, params = tiny_lm

    def go():
        reqs = _trace(cfg, n=8, seed=100 + seed, mean_out=8, rate=6.0)
        sched = build_replica(RS.replace(slots=4, n_blocks=14),
                              ap=ap, params=params)
        done = sched.run(reqs, max_steps=3000)
        assert all(r.output is not None for r in done), \
            "overcommitted pool failed to drain"
        return {r.rid: (r.output, r.preempted) for r in done}, \
            sched.metrics(done)

    a, ma = go()
    b, mb = go()
    assert ma.preemptions == mb.preemptions
    assert ma.wasted_tokens == mb.wasted_tokens
    for rid in a:
        np.testing.assert_array_equal(a[rid][0], b[rid][0])
        assert a[rid][1] == b[rid][1]


# ---------------------------------------------------------------------------
# serve CLI wiring
# ---------------------------------------------------------------------------


def test_serve_cli_fault_flags(capsys):
    from repro.launch import serve
    serve.main(["--arch", "llama3.2-1b", "--smoke", "--mode", "trace",
                "--requests", "6", "--block-size", "8",
                "--fault-plan", "seed=7,nan_logits=0.05,oom=0.1",
                "--deadline-ms", "500"])
    out = capsys.readouterr().out
    assert "robustness:" in out and "faults injected:" in out


def test_serve_cli_rejects_faults_in_batch_mode():
    from repro.launch import serve
    with pytest.raises(SystemExit, match="trace-mode only"):
        serve.main(["--arch", "llama3.2-1b", "--smoke", "--mode", "batch",
                    "--fault-plan", "nan_logits=0.1"])
    with pytest.raises(SystemExit, match="trace-mode only"):
        serve.main(["--arch", "llama3.2-1b", "--smoke", "--mode", "batch",
                    "--deadline-ms", "5"])
