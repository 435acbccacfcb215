"""Inference engine + continuous-batching scheduler behaviour tests,
including the paged KV-cache subsystem (block-table parity vs the dense
layout, allocator invariants, preemption/resume correctness)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models.transformer import make_plan, init_params
from repro.inference.kv_cache import BlockAllocator, TRASH_BLOCK
from repro.inference.scheduler import Request, make_trace
from repro.inference.spec import ReplicaSpec, build_engine, build_replica

RS = ReplicaSpec(arch="llama3.2-1b", slots=3, s_max=96)


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = get_smoke("llama3.2-1b")
    ap = make_plan(cfg, 1)
    params = init_params(jax.random.PRNGKey(0), ap)
    return cfg, ap, params


def test_engine_generate_matches_stepwise(tiny_lm):
    cfg, ap, params = tiny_lm
    eng = build_engine(RS.replace(s_max=64), ap=ap, params=params)
    prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (3, 12))
    res = eng.generate(prompts, 8)
    assert res.new_tokens.shape == (3, 8)
    assert res.tokens.shape == (3, 20)
    # greedy determinism
    res2 = eng.generate(prompts, 8)
    np.testing.assert_array_equal(res.new_tokens, res2.new_tokens)


def test_scheduler_completes_and_matches_engine(tiny_lm):
    cfg, ap, params = tiny_lm
    # one request through the scheduler == plain engine generation
    prompt = np.random.default_rng(1).integers(0, cfg.vocab_size, 12)
    sched = build_replica(RS.replace(slots=2, s_max=64), ap=ap,
                          params=params)
    reqs = [Request(rid=0, prompt=prompt.astype(np.int32), max_new=6)]
    done = sched.run(reqs)
    eng = build_engine(RS.replace(s_max=64), ap=ap, params=params)
    res = eng.generate(prompt[None], 6)
    np.testing.assert_array_equal(done[0].output, res.new_tokens[0])


def test_scheduler_trace_no_drops(tiny_lm):
    cfg, ap, params = tiny_lm
    sched = build_replica(RS, ap=ap, params=params)
    reqs = make_trace(9, mean_in=10, mean_out=6, rate=4.0,
                      vocab=cfg.vocab_size, seed=2)
    done = sched.run(reqs)
    assert all(r.output is not None for r in done)
    assert all(len(r.output) == r.max_new or len(r.output) > 0
               for r in done)
    # FCFS-ish: first arrival starts no later than last arrival
    assert done[0].first_token_s <= done[-1].first_token_s


def test_scheduler_interleaves_different_lengths(tiny_lm):
    cfg, ap, params = tiny_lm
    sched = build_replica(RS.replace(slots=2), ap=ap, params=params)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               8 + 8 * (i % 2)).astype(np.int32),
                    max_new=3 + 2 * (i % 3), arrival_s=0.0)
            for i in range(5)]
    done = sched.run(reqs)
    for r in done:
        assert r.output is not None and len(r.output) == r.max_new


# ---------------------------------------------------------------------------
# paged KV cache: parity, allocator invariants, preemption
# ---------------------------------------------------------------------------


def _trace_outputs(ap, params, vocab, *, n=8, mean_out=6, rate=4.0,
                   seed=2, **kw):
    sched = build_replica(RS.replace(**kw), ap=ap, params=params)
    reqs = make_trace(n, mean_in=10, mean_out=mean_out, rate=rate,
                      vocab=vocab, seed=seed)
    done = sched.run(reqs)
    metrics = sched.metrics(done)
    assert metrics.completed == len(reqs)
    return {r.rid: r.output for r in done}, metrics


def test_paged_trace_matches_dense(tiny_lm):
    """Block-table cache produces identical greedy tokens to the dense
    layout on a ragged multi-request trace, and a strictly smaller peak
    footprint."""
    cfg, ap, params = tiny_lm
    dense, md = _trace_outputs(ap, params, cfg.vocab_size)
    paged, mp = _trace_outputs(ap, params, cfg.vocab_size, block_size=8)
    for rid in dense:
        np.testing.assert_array_equal(dense[rid], paged[rid])
    assert mp.peak_kv_tokens < md.peak_kv_tokens
    assert mp.cache_stats["preemptions"] == 0


def test_chunked_admission_matches_full(tiny_lm):
    """Chunked-prefill admission (fixed executable) == per-length full
    prefill admission, dense and paged."""
    cfg, ap, params = tiny_lm
    full, _ = _trace_outputs(ap, params, cfg.vocab_size)
    for bs in (0, 8):
        chunked, _ = _trace_outputs(ap, params, cfg.vocab_size,
                                    block_size=bs, admit_mode="chunked",
                                    admit_chunk=16)
        for rid in full:
            np.testing.assert_array_equal(full[rid], chunked[rid])


def test_chunked_admission_pad_to_capacity(tiny_lm):
    """A prompt whose padded chunk tail reaches the logical capacity must
    not corrupt live K/V (pads route to the trash block on the paged
    path), and invalid s_max/admit_chunk geometry is rejected."""
    cfg, ap, params = tiny_lm
    prompt = np.random.default_rng(9).integers(
        0, cfg.vocab_size, 79).astype(np.int32)  # pads to 96 == s_max

    def run(**kw):
        sched = build_replica(RS.replace(slots=2, **kw), ap=ap,
                              params=params)
        r = Request(rid=0, prompt=prompt, max_new=6)
        sched.run([r])
        return r.output

    ref = run()
    for kw in (dict(admit_mode="chunked", admit_chunk=32),
               dict(admit_mode="chunked", admit_chunk=32, block_size=16),
               dict(admit_mode="chunked", admit_chunk=16, block_size=8)):
        np.testing.assert_array_equal(ref, run(**kw))
    with pytest.raises(ValueError):
        build_replica(RS.replace(slots=2, s_max=80, admit_mode="chunked",
                                 admit_chunk=32), ap=ap, params=params)


def test_engine_paged_generate_matches_dense(tiny_lm):
    cfg, ap, params = tiny_lm
    prompts = np.random.default_rng(4).integers(0, cfg.vocab_size, (3, 12))
    res_d = build_engine(RS.replace(s_max=64), ap=ap,
                         params=params).generate(prompts, 8)
    res_p = build_engine(RS.replace(s_max=64, block_size=16), ap=ap,
                         params=params).generate(prompts, 8)
    np.testing.assert_array_equal(res_d.new_tokens, res_p.new_tokens)


def test_block_allocator_invariants():
    a = BlockAllocator(n_blocks=9, block_size=4, slots=3,
                       max_blocks_per_slot=4)
    assert a.ensure(0, 5)          # 2 blocks
    assert a.ensure(1, 9)          # 3 blocks
    a.check()
    assert a.used_blocks == 5 and a.free_blocks == 3
    # growth must be atomic: failing ensure leaves state untouched
    assert not a.ensure(2, 16)     # needs 4, only 3 free
    a.check()
    assert a.used_blocks == 5
    assert a.ensure(2, 9)
    assert a.free_blocks == 0
    # idempotent ensure (already covered)
    assert a.ensure(0, 5) and a.used_blocks == 8
    # free -> blocks come back, table row reverts to trash
    freed = a.free(1)
    assert freed == 3 and a.free_blocks == 3
    assert (a.table[1] == TRASH_BLOCK).all()
    a.check()
    # freed blocks are reused
    assert a.ensure(0, 16)
    a.check()
    st = a.stats()
    assert st.peak_used_blocks == 8
    assert st.used_blocks == 7   # slot0 grew 2->4, slot1's 3 were freed
    with pytest.raises(ValueError):
        a.ensure(2, 17)            # > max_blocks_per_slot capacity
    with pytest.raises(ValueError):
        BlockAllocator(n_blocks=4, block_size=4, slots=2,
                       max_blocks_per_slot=4)  # cannot hold one request


def test_block_allocator_defragment_preserves_logical_view():
    a = BlockAllocator(n_blocks=12, block_size=2, slots=3,
                       max_blocks_per_slot=4)
    rng = np.random.default_rng(0)
    phys = rng.standard_normal((12, 2))
    a.ensure(0, 6)
    a.ensure(1, 8)
    a.ensure(2, 4)
    a.free(1)                       # punch a hole -> fragmentation
    a.ensure(2, 8)                  # reuses freed blocks out of order
    a.check()
    def logical(slot, n):
        return np.concatenate([phys[b] for b in a.table[slot][:n]])
    before = {0: logical(0, 3), 2: logical(2, 4)}
    perm = a.defragment()
    a.check()
    assert perm is not None
    phys = phys[perm]
    # live blocks are now packed at the lowest indices
    live = sorted(b for own in (a.owned(0), a.owned(2)) for b in own)
    assert live == list(range(1, len(live) + 1))
    np.testing.assert_array_equal(before[0], logical(0, 3))
    np.testing.assert_array_equal(before[2], logical(2, 4))
    # a second defrag is a no-op
    assert a.defragment() is None


def test_preemption_resume_correctness(tiny_lm):
    """A pool too small for three concurrent long decodes must preempt,
    requeue, recompute — and still emit exactly the undisturbed tokens."""
    cfg, ap, params = tiny_lm
    rng = np.random.default_rng(5)
    protos = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                                 16).astype(np.int32),
                      max_new=40, arrival_s=0.0) for i in range(3)]
    eng = build_engine(RS, ap=ap, params=params)
    ref = {r.rid: eng.generate(r.prompt[None], r.max_new).new_tokens[0]
           for r in protos}
    sched = build_replica(RS.replace(block_size=8, n_blocks=13), ap=ap,
                          params=params)
    done = sched.run([Request(rid=r.rid, prompt=r.prompt,
                              max_new=r.max_new) for r in protos])
    m = sched.metrics(done)
    assert m.preemptions > 0
    assert sum(r.preempted for r in done) == m.preemptions
    for r in done:
        np.testing.assert_array_equal(ref[r.rid], r.output)
    sched.alloc.check()
    assert sched.alloc.used_blocks == 0  # everything released at drain


def test_scheduler_defragment_mid_run(tiny_lm):
    """Defragmenting the live pool between steps must not change tokens."""
    cfg, ap, params = tiny_lm

    def defrag_every_step(sched):
        inner = sched.step
        def step(now):
            sched.defragment()
            inner(now)
        sched.step = step
        return sched

    # two trace shapes -> two fragmentation patterns under defrag
    for trace_kw in (dict(), dict(n=6, mean_out=8, rate=3.0, seed=6)):
        ref, _ = _trace_outputs(ap, params, cfg.vocab_size, **trace_kw)
        sched = defrag_every_step(build_replica(
            RS.replace(block_size=8), ap=ap, params=params))
        reqs = make_trace(trace_kw.get("n", 8), mean_in=10,
                          mean_out=trace_kw.get("mean_out", 6),
                          rate=trace_kw.get("rate", 4.0),
                          vocab=cfg.vocab_size,
                          seed=trace_kw.get("seed", 2))
        done = sched.run(reqs)
        for r in done:
            np.testing.assert_array_equal(ref[r.rid], r.output)
        assert sched.alloc.defrags > 0


def test_sampled_serving(tiny_lm):
    """temperature/top_k are honored on-device: deterministic under a seed,
    different across seeds, and max_new=1 returns exactly one token."""
    cfg, ap, params = tiny_lm

    def run(seed):
        sched = build_replica(RS.replace(slots=2, temperature=1.5,
                                         top_k=20, seed=seed),
                              ap=ap, params=params)
        reqs = [Request(rid=i, prompt=np.arange(8, dtype=np.int32) + i,
                        max_new=(1 if i == 0 else 12), arrival_s=0.0)
                for i in range(3)]
        return {r.rid: r.output for r in sched.run(reqs)}

    a1, a2, b = run(0), run(0), run(1)
    assert len(a1[0]) == 1
    for rid in a1:
        np.testing.assert_array_equal(a1[rid], a2[rid])
    assert any(not np.array_equal(a1[rid], b[rid]) for rid in a1), \
        "different seeds should sample different continuations"


def test_trace_metrics_sane(tiny_lm):
    cfg, ap, params = tiny_lm
    sched = build_replica(RS.replace(block_size=8), ap=ap, params=params)
    reqs = make_trace(8, mean_in=10, mean_out=6, rate=4.0,
                      vocab=cfg.vocab_size, seed=2)
    done = sched.run(reqs)
    m = sched.metrics(done)
    assert m.completed == 8 and m.total_new_tokens > 0
    assert m.ttft_steps_p50 >= 1.0
    assert m.ttft_steps_p99 >= m.ttft_steps_p50
    assert 0.9 <= m.tpot_steps_p50  # ~1 step/token when never starved
    assert m.throughput_tok_s > 0 and m.wall_s > 0
    assert 0.0 < m.cache_utilization <= 1.0
    assert m.peak_kv_tokens <= m.kv_capacity_tokens
    d = m.to_dict()
    assert d["cache_stats"]["block_size"] == 8


def test_kv_read_share_counts_live_blocks(tiny_lm):
    """``kv_read_share`` is the mean over decode steps of the blocks the
    paged attention reads, ``ceil((pos + 1) / block_size)`` summed over the
    live slots, over ``slots * max_blocks``; slots left idle (their stale
    positions included) add nothing."""
    cfg, ap, params = tiny_lm
    bs, spec = 8, RS.replace(block_size=8)
    sched = build_replica(spec, ap=ap, params=params)
    seen = []
    serve = sched._serve

    def recording(*a):
        seen.append((sched.positions.copy(), sched.active_mask.copy()))
        return serve(*a)

    sched._serve = recording
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, 5 + 11 * i).astype(np.int32),
        max_new=4 + 9 * (i % 2), arrival_s=0.0) for i in range(4)]
    done = sched.run(reqs)
    m = sched.metrics(done)
    table = spec.slots * spec.s_max // bs
    per_step = [(-(-(pos[act] + 1) // bs)).sum() / table
                for pos, act in seen]
    assert m.steps == len(seen) > 0
    assert m.kv_read_share == pytest.approx(np.mean(per_step))
    idle = [(pos, act) for pos, act in seen if not act.all()]
    assert any((pos[~act] > 0).any() for pos, act in idle), \
        "the trace should leave a slot idle with a stale position"
    assert m.kv_read_share < np.mean(
        [(-(-(pos + 1) // bs)).sum() / table for pos, _ in seen])
