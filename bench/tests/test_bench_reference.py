"""The plain float32 reference against the program, on the CPU."""
import dataclasses

import numpy as np
import pytest

from bench_helpers import TINY, harness, run_tiny, tiny_cell


def test_reference_matches_program_forward_in_float32():
    """Same weights, float32 throughout: the program's full-sequence
    forward and the reference give the same logits (RoPE convention, GQA
    grouping, QKV bias, norm gains)."""
    import jax
    import jax.numpy as jnp
    import reference
    from repro.core.pcontext import LOCAL
    from repro.models.transformer import forward_lm
    cell = tiny_cell(dtype="float32")
    seed = 12345
    b = harness.build(cell, seed, jax.devices()[:1])
    toks = np.random.default_rng(0).integers(0, TINY["vocab_size"], 70)
    logits = np.asarray(forward_lm(b.params, jnp.asarray(toks[None]), b.ap,
                                   LOCAL)[0][0], np.float32)
    served = logits[29:69].argmax(-1)
    got = reference.readings(seed, cell.config,
                             [(toks[:69], 30, served)])[0]["gap"]
    assert got.shape == (40,)
    assert got.max() < 1e-4, got.max()


@pytest.mark.parametrize("loop", ["backlog", "open"])
def test_served_tokens_pass_the_check(loop):
    """A whole run at a tiny size: the served path (chunked prefill into
    the paged cache, decode through it, greedy sampling) agrees with the
    reference, and the result carries its readings and checks."""
    res = run_tiny(tiny_cell(loop))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["compiles_in_window"] == 0
    assert res["readings"]["served_tokens_checked"] >= 50
    assert list(res["checks"])[0] == "logit_gap_mean"
    cell = tiny_cell(loop)
    assert set(res["metrics"]) == {m["name"] for m in
                                   harness.metrics_of(cell, False)}


def test_control_reads_above_the_program():
    """The fp8 control puts other tokens first where the served bf16
    path does not: the mean gap's limit lies between the two."""
    res = run_tiny(tiny_cell("backlog"), control=True)
    r = res["readings"]
    limit = TINY["correct"]["logit_gap_mean"]
    assert r["logit_gap_mean"] < limit < r["control_gap_mean"]
