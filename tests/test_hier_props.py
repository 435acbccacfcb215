"""Property tests on the hierarchical-collective schedule mathematics
(device-free: the schedule invariants the shard_map code relies on)."""
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pure-pytest fallback (requirements-dev.txt)
    from _hypothesis_fallback import given, settings, st


@given(st.sampled_from([2, 4, 8, 16, 32, 64, 128, 256]))
@settings(deadline=None)
def test_xor_schedule_is_perfect_matching_each_step(n):
    """Recursive doubling step i: peer = rank ^ 2^i is an involution with
    no fixed points — every device exchanges with exactly one other."""
    step = 1
    while step < n:
        peers = np.arange(n) ^ step
        assert np.all(peers != np.arange(n))
        assert np.array_equal(peers[peers], np.arange(n))
        step <<= 1


@given(st.sampled_from([2, 4, 8, 16, 32, 64]))
@settings(deadline=None)
def test_rd_converges_to_full_sum(n):
    """Simulate the RD dataflow on scalars: after log2(n) XOR exchanges,
    every rank holds the global sum."""
    rng = np.random.default_rng(n)
    vals = rng.standard_normal(n)
    acc = vals.copy()
    step = 1
    while step < n:
        acc = acc + acc[np.arange(n) ^ step]
        step <<= 1
    np.testing.assert_allclose(acc, np.full(n, vals.sum()), rtol=1e-9)


@given(st.sampled_from([2, 4, 8, 16, 32]))
@settings(deadline=None)
def test_halving_schedule_slice_tracking(n):
    """Recursive halving: the kept-half bit-walk leaves rank r holding
    logical chunk r (the invariant rd_halving_all_reduce's AG phase relies
    on)."""
    for r in range(n):
        lo, size, stride = 0, n, n >> 1
        while size > 1:
            half = size // 2
            if (r // stride) % 2:
                lo += half
            size, stride = half, stride >> 1
        assert lo == r


def test_tp_reduce_scatter_slow_phase_selection(monkeypatch):
    """PR 5 bugfix regression: tp_reduce_scatter's slow phase must route
    ``flat`` to lax.psum and EVERY hierarchical strategy through
    ``_slow_phase`` (the old code buried the flat remap in a conditional
    that could never fire, and sent hier_ring around _slow_phase)."""
    import jax.numpy as jnp
    from repro.core import hierarchical as hier
    from repro.core.pcontext import ParallelCtx

    calls = []
    monkeypatch.setattr(
        hier, "_slow_phase",
        lambda x, slow, ctx: (calls.append(("slow_phase",
                                            ctx.ar_strategy)), x)[1])
    monkeypatch.setattr(
        hier.lax, "psum",
        lambda x, axes: (calls.append(("psum", tuple(axes))), x)[1])
    x = jnp.ones((4, 8))
    for strat in ("flat", "hier_ring", "hier_rd", "hier_rd_halving"):
        calls.clear()
        ctx = ParallelCtx(tp_slow=("pod",), ar_strategy=strat)
        out = hier.tp_reduce_scatter(x, ctx, dim=0)
        assert out.shape == x.shape
        if strat == "flat":
            assert calls == [("psum", ("pod",))], (strat, calls)
        else:
            assert calls == [("slow_phase", strat)], (strat, calls)
