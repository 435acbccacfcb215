"""Parameter, byte and FLOP counts from shapes (`flops.py`) against the
program's own shapes and the sizes the configurations state."""
import jax
import numpy as np
import pytest

from bench_helpers import BENCH, TINY, harness

import flops
import weights as W

CODEQWEN = harness.load_json(BENCH / "configs" / "codeqwen1.5-7b.1chip.json")


def test_codeqwen_16_layers_is_8007360512_bytes():
    """16 layers of 4096 wide, 32 query and 4 KV heads of 128, QKV bias,
    a feed-forward of 13440, an untied 92416-row embedding and head."""
    assert flops.param_count(CODEQWEN) * 2 == 8_007_360_512


def test_codeqwen_paged_pool_bytes():
    """64 slots x 2048 positions in blocks of 16, plus the trash block:
    32 KiB of K and V a position over 16 layers, 4.30 GB in all."""
    assert flops.kv_bytes_per_token(CODEQWEN, 1) == 16 * 2 * 4 * 128 * 2
    sv = CODEQWEN["serve"]
    blocks = sv["slots"] * sv["s_max"] // sv["block_size"] + 1
    pool = blocks * sv["block_size"] * flops.kv_bytes_per_token(CODEQWEN, 1)
    assert pool == 4_295_491_584


@pytest.mark.parametrize("tp", [1, 2])
def test_bytes_per_chip_split_all_but_norms(tp):
    """At tp chips each holds 1/tp of every weight but the norm gains."""
    m = dict(CODEQWEN, num_key_value_heads=4, num_hidden_layers=2)
    norms = 2 * (2 * 2 + 1) * 4096
    assert flops.param_bytes_per_chip(m, tp) \
        == (2 * flops.param_count(m) - norms) // tp + norms


@pytest.mark.parametrize("name", ["codeqwen1.5-7b.1chip"])
def test_param_count_matches_program_shapes(name):
    from repro.models.transformer import init_params, make_plan
    m = harness.load_json(BENCH / "configs" / f"{name}.json")
    ap = make_plan(harness.model_config(m), 1)
    shapes = jax.eval_shape(lambda k: init_params(k, ap),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == flops.param_count(m)


@pytest.mark.parametrize("tp", [1, 2])
def test_weights_have_the_program_layout(tp):
    """The benchmark's weights have the tree, shapes and dtypes of the
    program's own init, on the tiny configuration."""
    from repro.models.transformer import init_params, make_plan
    ap = make_plan(harness.model_config(TINY), tp)
    want = jax.eval_shape(lambda k: init_params(k, ap), jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: W.program_params(
        5, TINY, ap.gqa.q_map, ap.gqa.kv_map, ap.vocab_pad))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)


def test_decode_step_counts():
    """One codeqwen decode token at 100 keys: 2 FLOPs per matmul weight,
    plus q.k and p.v; bytes are every weight but the embedding table."""
    m = CODEQWEN
    f, b = flops.decode_step(m, 1, [100])
    per_tok = 2 * (16 * flops.layer_matmul_params(m) + 4096 * 92416) \
        + 4 * 16 * 32 * 128 * 100
    assert f == per_tok
    assert b == 8_007_360_512 - 2 * 92416 * 4096 + 2 * 4096 \
        + 101 * flops.kv_bytes_per_token(m, 1)


def test_prefill_chunks_cover_the_prompt():
    chunks = flops.prompt_chunks(600, 256)
    assert chunks == [(0, 256, False), (256, 256, False), (512, 88, True)]
