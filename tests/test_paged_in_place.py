"""The paged K/V pools addressed in place inside the layer loop.

A paged cache's pools (L, n_blocks, bs, U, hd) run through the layers as
one pool (L*n_blocks, bs, U, hd), each layer reaching its own blocks
through a table offset by ``i*n_blocks``.  The reference here is the
older layout of the loop: every leaf, the pools included, sliced out of
the stack per layer and written back, the table as given (the
``sliced_layers`` fixture).  Logits and
the whole stacked pools, trash blocks included, must be bitwise equal.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LOCAL
from repro.models import (ModelConfig, decode_step, init_cache, init_params,
                          make_plan, prefill_chunk)

B, S_MAX, C = 3, 32, 8


def _cfg(family="dense", **kw):
    base = dict(name=f"tiny-{family}", family=family, n_layers=3, d_model=64,
                n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=97,
                dtype=jnp.float32)
    base.update(kw)
    return ModelConfig(**base)


CFGS = {"dense": _cfg(),
        "hybrid": _cfg("hybrid", d_inner=128, ssm_state=8, sliding_window=8)}


def _paged_cache(ap, bs, seed):
    """A paged cache with random pool contents (trash blocks too), a
    shuffled block table and an inactive first row on the trash block."""
    max_blocks = S_MAX // bs
    n_blocks = (B - 1) * max_blocks + 3
    cache = init_cache(ap, B, S_MAX, block_size=bs, n_blocks=n_blocks)
    rng = np.random.default_rng(seed)
    for k in ("k", "v"):
        cache[k] = jnp.asarray(rng.standard_normal(cache[k].shape),
                               cache[k].dtype)
    tbl = np.zeros((B, max_blocks), np.int32)
    tbl[1:] = (1 + rng.permutation(n_blocks - 1))[
        :(B - 1) * max_blocks].reshape(B - 1, max_blocks)
    cache["block_tbl"] = jnp.asarray(tbl)
    return cache


def _jit(fn):
    """``fn`` jitted afresh: ``jax.jit`` of one function object reuses its
    trace, which would hide the patched layer loop."""
    return jax.jit(lambda *args: fn(*args))


def _both(sliced_layers, fn, *args):
    """``fn(*args)`` jitted, in place and through the sliced reference."""
    got = _jit(fn)(*args)
    with sliced_layers():
        ref = _jit(fn)(*args)
    return got, ref


def _assert_bitwise(got, ref):
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
@pytest.mark.parametrize("bs", [4, 8])
@pytest.mark.parametrize("family", list(CFGS))
def test_paged_decode_step_bitwise(sliced_layers, family, bs, scan):
    ap = make_plan(CFGS[family], 1)
    params = init_params(jax.random.PRNGKey(0), ap)
    cache = _paged_cache(ap, bs, seed=bs)
    if family == "hybrid":
        cache = {k: jnp.ones_like(t) * 0.5 if k in ("conv", "ssm") else t
                 for k, t in cache.items()}
    tokens = jnp.asarray([5, 17, 3], jnp.int32)
    positions = jnp.asarray([0, S_MAX - 1, bs + 1], jnp.int32)
    fn = functools.partial(decode_step, ap=ap, ctx=LOCAL, scan_layers=scan)
    got, ref = _both(sliced_layers, fn, params, cache, tokens, positions)
    _assert_bitwise(got, ref)
    # the new tokens landed: the pools moved, in every layer
    moved = np.asarray(got[1]["k"]) != np.asarray(cache["k"])
    assert moved.any(axis=(1, 2, 3, 4)).all()


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
@pytest.mark.parametrize("bs", [4, 8])
@pytest.mark.parametrize("batched", [False, True], ids=["slot", "batched"])
def test_paged_prefill_chunk_bitwise(sliced_layers, batched, bs, scan):
    ap = make_plan(CFGS["dense"], 1)
    params = init_params(jax.random.PRNGKey(1), ap)
    cache = _paged_cache(ap, bs, seed=10 + bs)
    rng = np.random.default_rng(bs)
    # half of the last row's chunk lies past S_MAX: pads that go to the
    # trash, written after the inactive first row's
    starts = np.asarray([4, 0, S_MAX - C // 2], np.int32)
    positions = starts[:, None] + np.arange(C, dtype=np.int32)
    tokens = rng.integers(0, ap.cfg.vocab_size, (B, C)).astype(np.int32)
    if batched:
        args = (jnp.asarray(tokens), jnp.asarray(positions))
        kw = {}
    else:
        args = (jnp.asarray(tokens[2:]), jnp.asarray(positions[2:]))
        kw = {"slot": jnp.int32(2)}

    def fn(params, cache, tokens, positions):
        return prefill_chunk(params, cache, tokens, positions, ap, LOCAL,
                             scan_layers=scan, **kw)

    got, ref = _both(sliced_layers, fn, params, cache, *args)
    _assert_bitwise(got, ref)
    # each layer's pads reached its own trash block
    trash = np.asarray(got[1]["k"])[:, 0]
    assert (trash != np.asarray(cache["k"])[:, 0]).any(axis=(1, 2, 3)).all()


def test_dense_cache_decode_program_unchanged(sliced_layers):
    """A dense cache takes the sliced loop: the scanned decode step is the
    reference's program, instruction for instruction."""
    from repro.launch.hlo_analysis import program_text
    ap = make_plan(CFGS["dense"], 1)
    params = init_params(jax.random.PRNGKey(0), ap)
    cache = init_cache(ap, B, S_MAX)
    tokens = jnp.zeros((B,), jnp.int32)
    fn = functools.partial(decode_step, ap=ap, ctx=LOCAL)

    def text():
        return program_text(_jit(fn).lower(
            params, cache, tokens, tokens).compile().as_text(),
            renumber=True)

    got = text()
    with sliced_layers():
        ref = text()
    assert got == ref
