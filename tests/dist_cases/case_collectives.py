"""8-device validation: every hierarchical all-reduce strategy is exact
(or near-exact for int8) against flat psum."""
import numpy as np, jax, jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core.compat import make_mesh
from repro.core import (rd_all_reduce, rd_halving_all_reduce,
                        compressed_rd_all_reduce, tp_all_reduce, ParallelCtx)

mesh = make_mesh((2, 4), ("pod", "model"))
rng = np.random.default_rng(0)
x = rng.standard_normal((8, 64)).astype(np.float32)

def run(fn):
    f = shard_map(fn, mesh=mesh, in_specs=P("pod", "model"),
                  out_specs=P("pod", "model"), check_vma=False)
    return np.asarray(jax.jit(f)(x))

ref = run(lambda v: lax.psum(v, ("pod", "model")))
assert np.allclose(run(lambda v: rd_all_reduce(lax.psum(v, "model"), "pod")), ref, rtol=1e-5)
assert np.allclose(run(lambda v: rd_all_reduce(lax.psum(v, "model"), "pod", chunks=4)), ref, rtol=1e-5)
assert np.allclose(run(lambda v: rd_halving_all_reduce(lax.psum(v, "model"), "pod")), ref, rtol=1e-5)
c = run(lambda v: compressed_rd_all_reduce(lax.psum(v, "model"), "pod"))
assert np.abs(c - ref).max() / np.abs(ref).max() < 0.05

for strat in ("hier_rd", "hier_rd_halving", "hier_ring"):
    ctx = ParallelCtx(tp_fast=("model",), tp_slow=("pod",), ar_strategy=strat)
    out = run(lambda v: tp_all_reduce(v, ctx, scatter_dim=-1))
    assert np.allclose(out, ref, rtol=1e-5), strat
# 2-fast-axis hierarchy (256-way-TP analogue)
ctx = ParallelCtx(tp_fast=("pod", "model"), ar_strategy="hier_rd")
assert np.allclose(run(lambda v: tp_all_reduce(v, ctx, scatter_dim=-1)), ref, rtol=1e-5)
# non-power-of-two fallback on a 3-wide axis
mesh3 = make_mesh((3,), ("m",))
f3 = shard_map(lambda v: rd_all_reduce(v, "m"), mesh=mesh3, in_specs=P("m"),
               out_specs=P("m"), check_vma=False)
x3 = rng.standard_normal((6, 4)).astype(np.float32)
g3 = shard_map(lambda v: lax.psum(v, "m"), mesh=mesh3, in_specs=P("m"),
               out_specs=P("m"), check_vma=False)
assert np.allclose(jax.jit(f3)(x3), jax.jit(g3)(x3), rtol=1e-5)
print("collectives OK")

# --- Pallas RD all-reduce kernel (remote-DMA, interpret mode) -------------
from jax.experimental.pallas import tpu as pltpu
from repro.kernels.rd_allreduce import rd_all_reduce_pallas
interp = pltpu.InterpretParams()
mesh8 = make_mesh((8,), ("pd",))
x8 = rng.standard_normal((8, 300)).astype(np.float32)
gk = shard_map(lambda v: lax.psum(v, "pd"), mesh=mesh8, in_specs=P("pd"),
               out_specs=P("pd"), check_vma=False)
for nc in (1, 2, 4, 8):
    fk = shard_map(lambda v: rd_all_reduce_pallas(v, "pd", n_chunks=nc,
                                                  interpret=interp),
                   mesh=mesh8, in_specs=P("pd"), out_specs=P("pd"),
                   check_vma=False)
    assert np.allclose(jax.jit(fk)(x8), jax.jit(gk)(x8), rtol=1e-4,
                       atol=1e-5), f"pallas rd kernel chunks={nc}"
print("pallas rd kernel OK")
# the same kernel over the slow axis of a 2-D (pod, model) mesh: the peer
# differs in the pod index only, and keeps this device's model index
fk2d = shard_map(lambda v: rd_all_reduce_pallas(v, "pod", n_chunks=2,
                                                interpret=interp),
                 mesh=mesh, in_specs=P("pod", "model"),
                 out_specs=P("pod", "model"), check_vma=False)
assert np.allclose(jax.jit(fk2d)(x), run(lambda v: lax.psum(v, "pod")),
                   rtol=1e-4, atol=1e-5), "pallas rd kernel on (pod, model)"
print("pallas rd kernel on a 2-D mesh OK")
