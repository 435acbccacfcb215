"""The Mistral-Large-2407 cell's path at a tiny size: the served path at
tp=4 against the reference in Mistral's head grouping (12 query heads to
a KV head, 2 KV heads a chip, no QKV bias, untied head), on four host
devices; and the two readers of the all-reduce's device time on a
planted chip."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench_helpers import BENCH, ROOT, harness

TP4 = r"""
import json, sys
sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
import numpy as np
import jax, jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P
from bench_helpers import TINY, harness, run_tiny, tiny_cell
import reference
from repro.models import transformer
from repro.parallel import sharding as shd

MISTRAL = dict(arch="mistral-large-123b", num_attention_heads=96,
               num_key_value_heads=8, head_dim=8, num_hidden_layers=4,
               qkv_bias=False, tie_word_embeddings=False, serve={{"tp": 4}})
out = {{}}

# the program's full-sequence forward, float32, split four ways
cell = tiny_cell("backlog", chips=4, dtype="float32", **MISTRAL)
seed = 12345
b = harness.build(cell, seed, jax.devices()[:4])
g = b.ap.gqa
assert (g.g, g.q_slots_local, g.kv_slots_local) == (12, 24, 2)
assert min(g.q_map) >= 0 and min(g.kv_map) >= 0     # no dead or copied slot
fwd = jax.jit(shard_map(
    lambda p, t: transformer.forward_lm(p, t, b.ap, b.ctx)[0], mesh=b.mesh,
    in_specs=(shd.param_specs(b.params, b.ctx, b.mesh), P()),
    out_specs=P(None, None, "model"), check_vma=False))
toks = np.random.default_rng(0).integers(0, TINY["vocab_size"], 70)
logits = np.asarray(fwd(b.params, jnp.asarray(toks[None])), np.float32)[0]
served = logits[29:69, :TINY["vocab_size"]].argmax(-1)
gap = reference.readings(seed, cell.config, [(toks[:69], 30, served)],
                         devices=jax.devices()[:4])[0]["gap"]
out["forward_gap_max"] = float(gap.max())
out["forward_positions"] = int(gap.size)
del b

cell = tiny_cell("backlog", chips=4, **MISTRAL)
sound = run_tiny(cell, seconds=2.0)
out["sound"] = sound["checks"]
out["sound_compiles"] = sound["compiles_in_window"]
out["sound_failed"] = sound["failed"]
out["sound_tokens"] = sound["readings"]["served_tokens_checked"]

# the all-reduce after each layer's MLP left out (the attention's and the
# embedding lookup's kept): each chip adds only its own quarter of the MLP
real = transformer._residual_proj

def no_mlp_exchange(x, lhs, w, spec, ctx, sp, ef=None):
    if spec != "bsf,fd->bsd":
        return real(x, lhs, w, spec, ctx, sp, ef)
    y = x + jnp.einsum(spec, lhs, w)
    return (y, ef) if ef is not None else y

transformer._residual_proj = no_mlp_exchange
out["no_mlp_exchange"] = run_tiny(cell, seconds=2.0)["checks"]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def tp4():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = TP4.format(bench=str(BENCH), tests=str(BENCH / "tests"),
                      src=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=str(ROOT))
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _ok(checks):
    return all(c["value"] <= c["limit"] for c in checks.values())


def test_tp4_forward_matches_reference_in_float32(tp4):
    """Same weights, float32: the program's forward split over four
    devices (24 query and 2 KV heads each) gives the reference's
    logits."""
    assert tp4["forward_positions"] == 40
    assert tp4["forward_gap_max"] < 1e-4, tp4


def test_tp4_served_run_is_correct(tp4):
    """Chunked prefill into the paged cache, then decode, at tp=4: the
    served tokens pass the check, nothing fails or compiles in the
    window."""
    assert _ok(tp4["sound"]), tp4
    assert tp4["sound_compiles"] == 0 and tp4["sound_failed"] == 0
    assert tp4["sound_tokens"] >= 50


def test_tp4_mlp_exchange_left_out_is_not_correct(tp4):
    assert not _ok(tp4["no_mlp_exchange"]), tp4
    c = tp4["no_mlp_exchange"]["logit_gap_mean"]
    assert c["value"] > c["limit"], tp4


# ---------------------------------------------------------------------------
# allreduce_ms.decode and allreduce_exposed_ms.decode on a planted chip
# ---------------------------------------------------------------------------

MAPS = {"step": [{"psum.1": "tp_all_reduce", "all-reduce-start.2":
                  "tp_all_reduce", "all-reduce-done.2": "tp_all_reduce",
                  "fusion.3": "mlp", "fusion.4": "attention"}]}


def _run(ops=(), async_ops=(), maps=MAPS, runs=((0, 1000),)):
    """A run whose first chip ran the `step` module over ``runs`` (ns)."""
    chip = SimpleNamespace(modules=[(s, e, "step") for s, e in runs],
                           ops=sorted(ops), async_ops=sorted(async_ops))
    run = harness.Run(cell=None, seconds=1.0, setup_s=0.0, token_times={},
                      arrivals={}, chips=4, peaks={},
                      trace=SimpleNamespace(chips=[chip]))
    run.op_scopes = maps
    return run


def _read(run):
    return (harness.reader("allreduce_ms.decode")(run),
            harness.reader("allreduce_exposed_ms.decode")(run))


@pytest.mark.parametrize("ops,async_ops,ms,exposed", [
    # a synchronous all-reduce with nothing beside it
    ([(100, 300, "psum.1 bf16[32,1,12288]"), (300, 500, "fusion.3")], [],
     200, 200),
    # ... beside an asynchronous copy, which hides nothing
    ([(100, 300, "psum.1"), (300, 500, "fusion.3")],
     [(0, 600, "copy-start.5")], 200, 200),
    # an asynchronous one that a matmul hides whole
    ([(100, 450, "fusion.3")], [(150, 400, "all-reduce-start.2")], 250, 0),
    # a start/done pair around a matmul: only the pair's own ops show
    ([(100, 150, "all-reduce-start.2"), (150, 400, "fusion.3"),
      (400, 450, "all-reduce-done.2")],
     [(100, 450, "all-reduce-start.2")], 350, 100),
    # one that compute hides in part, beside a lone one
    ([(0, 100, "psum.1"), (200, 300, "fusion.4")],
     [(150, 400, "all-reduce-start.2")], 350, 250),
], ids=["lone", "lone_beside_a_copy", "under_compute", "start_done_pair",
        "partly_hidden"])
def test_allreduce_readers(ops, async_ops, ms, exposed):
    got = _read(_run(ops, async_ops))
    assert got == pytest.approx((ms * 1e-6, exposed * 1e-6))


def test_allreduce_readers_average_over_step_runs():
    """Ops are counted in the run they start in, per run of the step;
    ops outside any run are not counted."""
    run = _run([(100, 300, "psum.1"), (1100, 1200, "psum.1"),
                (2000, 2500, "psum.1")], runs=((0, 1000), (1000, 1500)))
    assert _read(run) == pytest.approx((150e-6, 150e-6))


@pytest.mark.parametrize("maps", [{}, {"step": [{}]},
                                  {"step": [{"fusion.3": "mlp"}]}],
                         ids=["no_maps", "no_scopes", "no_all_reduce"])
def test_allreduce_readers_read_nothing_without_the_scope(maps):
    """No scope maps (a parent without `op_scopes`, a failed rebuild), an
    executable without scopes, or a step with no op in `tp_all_reduce`
    (tp=1) read nothing; so does a run without a trace."""
    run = _run([(100, 300, "psum.1")], maps=maps)
    assert _read(run) == (None, None)
    run = _run([(100, 300, "psum.1")])
    run.trace = None
    assert _read(run) == (None, None)
