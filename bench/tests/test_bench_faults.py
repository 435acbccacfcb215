"""A run whose timed path is broken underneath must come out not
correct: the harness drives the tiny cell without the chip check, with
one fault planted in the served path, and the comparison with the
reference has to catch it.  (A training fault, half of a batch left out,
has no counterpart in a served cell.)"""
import json
import os
import subprocess
import sys

import pytest

from bench_helpers import BENCH, ROOT, run_tiny, tiny_cell


def _stale_cache(b):
    """The decode step hands back the cache it was given: its new K/V
    never lands."""
    import jax
    import jax.numpy as jnp
    real = b._serve

    def step(params, cache, state):
        keep = jax.tree.map(jnp.copy, cache)
        emitted, done, finite, state2, _ = real(params, cache, state)
        return emitted, done, finite, state2, keep

    b._serve = step


def _altered_token(b):
    """Each slot's token is replaced where the decode step produces it
    (in every slot, so that the seed-drawn sample holds altered tokens
    however few requests a short run finishes)."""
    real, vocab = b._serve, b.cfg.vocab_size

    def step(params, cache, state):
        emitted, done, finite, state2, cache2 = real(params, cache, state)
        bad = (emitted + 1) % vocab
        state2 = dict(state2, tokens=bad)
        return bad, done, finite, state2, cache2

    b._serve = step


@pytest.mark.parametrize("fault", [_stale_cache, _altered_token],
                         ids=["state_unchanged", "token_altered"])
def test_fault_is_not_correct(fault):
    res = run_tiny(tiny_cell("backlog"), after_build=fault)
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap_mean"]["value"] \
        > res["checks"]["logit_gap_mean"]["limit"]


TP4 = r"""
import json, sys, time
sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
from bench_helpers import run_tiny, tiny_cell
cell = tiny_cell("backlog", chips=4, num_attention_heads=8,
                 num_key_value_heads=4, serve={{"tp": 4}})
sound = run_tiny(cell, seconds=2.0)
assert sound["compiles_in_window"] == 0
out = {{"sound": sound["checks"]}}
import repro.core.hierarchical as hier
hier.tp_all_reduce = lambda x, ctx, scatter_dim=-1, ef=None: \
    (x, ef) if ef is not None else x
out["no_exchange"] = run_tiny(cell, seconds=2.0)["checks"]
print(json.dumps(out))
"""


def test_exchange_left_out_is_not_correct():
    """tp=4 on four host devices: sound, the run is correct; with the
    all-reduce between chips left out, it is not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = TP4.format(bench=str(BENCH), tests=str(BENCH / "tests"),
                      src=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=str(ROOT))
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    ok = lambda c: all(v["value"] <= v["limit"] for v in c.values())
    assert ok(out["sound"]), out
    assert not ok(out["no_exchange"]), out
