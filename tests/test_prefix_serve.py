"""Prefix-cache serving: bitwise parity and cross-slot isolation.

The correctness bar (DESIGN.md §14): with ``prefix_cache="on"`` a
shared-prefix trace must produce **token-bitwise-identical** outputs to
the same trace with the cache off, while actually splicing blocks
(``prefix_tokens_saved > 0`` — a cache that never hits proves nothing).
Isolation is the half that breaks silently: a sharer's truncate /
preempt / speculative rollback / NaN quarantine must never mutate or
free a block another slot (or the trie) still references, which the
copy-on-write paths (`fork_for_write`, exclusive-only scrub) guarantee.
Every test closes by re-checking the allocator partition.
"""
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.inference.faults import FaultInjector, FaultPlan
from repro.inference.scheduler import Request, make_prefix_trace
from repro.inference.spec import ReplicaSpec, build_replica
from repro.inference.speculative import Drafter
from repro.models.transformer import make_plan, init_params

import jax

RS = ReplicaSpec(arch="llama3.2-1b", slots=3, s_max=96, block_size=8,
                 admit_mode="chunked", admit_chunk=16)
RP = RS.replace(prefix_cache="on")


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = get_smoke("llama3.2-1b")
    ap = make_plan(cfg, 1)
    params = init_params(jax.random.PRNGKey(0), ap)
    return cfg, ap, params


def _trace(cfg, n=10, seed=0, shared_frac=0.7, mean_out=8):
    return make_prefix_trace(n, prefix_len=32, shared_frac=shared_frac,
                             mean_in=12, mean_out=mean_out, rate=2.0,
                             vocab=cfg.vocab_size, seed=seed, clip_len=95)


def _outputs(sched, reqs):
    done = sched.run(reqs)
    assert all(r.output is not None for r in done)
    return {r.rid: r.output for r in done}, sched.metrics(done)


def _isolated_refs(cfg, ap, params, reqs):
    refs = {}
    for r in reqs:
        s1 = build_replica(RS.replace(slots=1), ap=ap, params=params)
        rr = Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new)
        s1.run([rr])
        refs[r.rid] = rr.output
    return refs


def test_shared_prefix_trace_bitwise_parity(tiny_lm):
    """The headline guarantee: prefix on == prefix off, token for token,
    with real splicing happening underneath."""
    cfg, ap, params = tiny_lm
    off, m_off = _outputs(build_replica(RS, ap=ap, params=params),
                          _trace(cfg))
    on_sched = build_replica(RP, ap=ap, params=params)
    on, m_on = _outputs(on_sched, _trace(cfg))
    assert m_on.prefix_hits > 0 and m_on.prefix_tokens_saved > 0
    assert m_on.prefix_hit_rate == pytest.approx(
        m_on.prefix_hits / m_on.prefix_lookups)
    assert m_off.prefix_lookups == 0, "off means off"
    for rid in off:
        np.testing.assert_array_equal(off[rid], on[rid])
    on_sched.alloc.check()
    # slots drained, but the trie's holds legitimately outlive the run
    assert on_sched.alloc.used_blocks == on_sched.prefix.held_blocks


def test_full_admit_mode_parity_with_prefix(tiny_lm):
    """prefix_cache="on" forces chunked executables for the spliced
    suffix even under admit_mode="full"; tokens must not change."""
    cfg, ap, params = tiny_lm
    off, _ = _outputs(build_replica(RS.replace(admit_mode="full"),
                                    ap=ap, params=params), _trace(cfg))
    on, m = _outputs(build_replica(RP.replace(admit_mode="full"),
                                   ap=ap, params=params), _trace(cfg))
    assert m.prefix_tokens_saved > 0
    for rid in off:
        np.testing.assert_array_equal(off[rid], on[rid])


def test_tight_pool_preemption_with_prefix(tiny_lm):
    """A pool tight enough to preempt live requests must first reclaim
    cold trie nodes, and recompute preempted work bitwise-exactly even
    when the re-admitted prompt hits the (surviving) cache."""
    cfg, ap, params = tiny_lm
    reqs = _trace(cfg, mean_out=16)
    refs = _isolated_refs(cfg, ap, params, reqs)
    tight = build_replica(RP.replace(n_blocks=15), ap=ap, params=params)
    done = tight.run([Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                              arrival_s=r.arrival_s) for r in reqs])
    m = tight.metrics(done)
    assert m.preemptions > 0, "pool not tight enough — not a test"
    assert m.prefix_hits > 0
    for r in done:
        np.testing.assert_array_equal(refs[r.rid], r.output), r.rid
    tight.alloc.check()


class _JunkDrafter(Drafter):
    """Always-rejected drafts: every verify step writes a divergent K/V
    tail into the drafting slot that rollback must fully retract."""

    def __init__(self, vocab: int):
        super().__init__()
        self.vocab = vocab

    def _propose(self, slot, hist, k):
        last = hist[-1] if hist else 0
        return [(last + 1 + i) % self.vocab for i in range(k)]


def test_spec_rollback_never_leaks_into_sharers(tiny_lm):
    """Adversarial isolation: speculative rollback truncates tails on
    slots whose prompt blocks are shared through the trie.  The rollback
    must drop only the drafting slot's references — sharers' attention
    over the same physical blocks stays bitwise-identical to isolated
    runs."""
    cfg, ap, params = tiny_lm
    reqs = _trace(cfg, seed=1, shared_frac=0.8)
    refs = _isolated_refs(cfg, ap, params, reqs)
    sched = build_replica(RP.replace(spec_mode="replay", spec_k=4),
                          ap=ap, params=params,
                          drafter=_JunkDrafter(cfg.vocab_size))
    done = sched.run([Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                              arrival_s=r.arrival_s) for r in reqs])
    m = sched.metrics(done)
    # near-zero, not zero: (last+1) % vocab can collide with the greedy
    # token by chance — every other verify pass still rolls a tail back
    assert m.acceptance_rate < 0.1, "junk drafts must be almost all rejected"
    assert m.spec_steps > 0 and m.prefix_hits > 0
    for r in done:
        np.testing.assert_array_equal(refs[r.rid], r.output), r.rid
    sched.alloc.check()


def test_poison_forks_shared_blocks_before_writing(tiny_lm):
    """NaN injection targeting a position inside a shared/held block
    must copy-on-write fork it first: the quarantined slot recomputes
    exactly, and the sharers (and later cache hits on the same prefix)
    never observe the poison."""
    cfg, ap, params = tiny_lm
    ref, _ = _outputs(build_replica(RS, ap=ap, params=params),
                      _trace(cfg, shared_frac=0.9))
    inj = FaultInjector(FaultPlan(seed=7, nan_logits=0.08))
    sched = build_replica(RP, ap=ap, params=params, injector=inj)
    got, m = _outputs(sched, _trace(cfg, shared_frac=0.9))
    assert m.quarantines > 0, "no quarantine fired — not a test"
    assert m.prefix_hits > 0
    for rid in ref:
        np.testing.assert_array_equal(ref[rid], got[rid])
    sched.alloc.check()


def test_trie_survives_runs_and_readmission_hits(tiny_lm):
    """The trie persists across `run()` calls (per-run counters reset):
    replaying the same trace must hit on every shared admission and save
    at least as many tokens as the cold run."""
    cfg, ap, params = tiny_lm
    sched = build_replica(RP, ap=ap, params=params)
    cold, m_cold = _outputs(sched, _trace(cfg))
    warm, m_warm = _outputs(sched, _trace(cfg))
    assert m_warm.prefix_hits >= m_cold.prefix_hits
    assert m_warm.prefix_tokens_saved >= m_cold.prefix_tokens_saved
    assert m_warm.prefix_hit_rate >= m_cold.prefix_hit_rate
    for rid in cold:
        np.testing.assert_array_equal(cold[rid], warm[rid])
    sched.alloc.check()


@pytest.mark.parametrize("slots", [2, 4])
def test_hits_monotone_in_sharing_fraction(tiny_lm, slots):
    """More sharing can only add hits: ``make_prefix_trace`` draws each
    request's share coin from the same per-request stream, so at fixed
    slots the hit rate and the tokens saved never fall as the sharing
    fraction rises; nothing is shared at 0 and something is spliced at 1,
    where every prompt shares the prefix and tokens still match the
    cache-off run."""
    cfg, ap, params = tiny_lm
    spec = RP.replace(slots=slots)
    cells = []
    for frac in (0.0, 0.5, 1.0):
        reqs = _trace(cfg, n=12, seed=3, shared_frac=frac)
        sched = build_replica(spec, ap=ap, params=params)
        on, m = _outputs(sched, reqs)
        sched.alloc.check()
        cells.append(m)
    for lo, hi in zip(cells, cells[1:]):
        assert hi.prefix_hit_rate >= lo.prefix_hit_rate
        assert hi.prefix_tokens_saved >= lo.prefix_tokens_saved
    assert cells[0].prefix_tokens_saved == 0
    assert cells[-1].prefix_tokens_saved > 0
    # `on` holds the tokens of the sharing-1.0 run
    off, _ = _outputs(build_replica(RS.replace(slots=slots), ap=ap,
                                    params=params),
                      _trace(cfg, n=12, seed=3, shared_frac=1.0))
    for rid in off:
        np.testing.assert_array_equal(off[rid], on[rid])


def test_capacity_cap_still_exact(tiny_lm):
    """A one-block capacity forces constant LRU churn; hits may vanish
    but correctness may not."""
    cfg, ap, params = tiny_lm
    off, _ = _outputs(build_replica(RS, ap=ap, params=params), _trace(cfg))
    sched = build_replica(RP.replace(prefix_capacity=1),
                          ap=ap, params=params)
    on, _ = _outputs(sched, _trace(cfg))
    assert sched.prefix.evictions > 0, "capacity never binding"
    # live sharers legitimately pin nodes past the soft cap mid-run;
    # once the slots drain the overflow is evictable again
    sched.prefix.reclaim(max(sched.prefix.held_blocks - 1, 0))
    assert sched.prefix.held_blocks <= 1
    for rid in off:
        np.testing.assert_array_equal(off[rid], on[rid])
    sched.alloc.check()
