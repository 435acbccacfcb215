"""8-device validation of the overlapped collective-matmul decode primitive
and the autotuned dispatcher:

1. collective_matmul is bit-consistent (dtype tolerance) with
   GEMM-then-tp_all_reduce for all four strategies AND ar_strategy="auto",
   at every chunk count, on the (2 pod x 4 model) mesh;
2. the attention-spec form ("bsqh,qhd->bsd") matches the unfused einsum;
3. rd_all_reduce chunked-path edge cases: payload not divisible by chunks,
   chunks > payload, non-power-of-two axis fallback;
4. the sequence-parallel reduce-scatter variant matches tp_reduce_scatter;
5. an end-to-end decode parity run: overlap_matmul=True + ar_strategy="auto"
   produces the exact greedy tokens of the plain flat path.
"""
import numpy as np, jax, jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core.compat import make_mesh
from repro.core import (collective_matmul, collective_matmul_reduce_scatter,
                        rd_all_reduce, tp_all_reduce, tp_reduce_scatter,
                        ParallelCtx, autotune)

mesh = make_mesh((2, 4), ("pod", "model"))
rng = np.random.default_rng(0)

B, S, F, D = 2, 3, 32, 64   # F: sharded contraction dim, D: output features
x = rng.standard_normal((B, S, F)).astype(np.float32)
w = rng.standard_normal((F, D)).astype(np.float32)
in_specs = (P(None, None, ("pod", "model")), P(("pod", "model"), None))


def run(fn, out_specs=P()):
    f = shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=False)
    return np.asarray(jax.jit(f)(x, w))


ref = np.einsum("bsf,fd->bsd", x, w)
for strat in ("flat", "hier_ring", "hier_rd", "hier_rd_halving", "auto"):
    ctx = ParallelCtx(tp_fast=("model",), tp_slow=("pod",),
                      ar_strategy=strat)
    base = run(lambda xs, ws: tp_all_reduce(
        jnp.einsum("bsf,fd->bsd", xs, ws), ctx, scatter_dim=-1))
    np.testing.assert_allclose(base, ref, rtol=1e-4, atol=1e-4)
    for k in (1, 2, 4, 8):
        ovr = run(lambda xs, ws: collective_matmul(
            xs, ws, ctx.replace(overlap_matmul=True, overlap_chunks=k)))
        np.testing.assert_allclose(ovr, base, rtol=1e-5, atol=1e-5), \
            (strat, k)
    print(f"collective_matmul parity [{strat}] OK")

# --- attention-spec form ---------------------------------------------------
Q, hd = 8, 16
o8 = rng.standard_normal((B, S, Q, hd)).astype(np.float32)
wo = rng.standard_normal((Q, hd, D)).astype(np.float32)
ctx = ParallelCtx(tp_fast=("model",), tp_slow=("pod",), ar_strategy="auto",
                  overlap_matmul=True)
fa = shard_map(
    lambda os_, ws: collective_matmul(os_, ws, ctx, spec="bsqh,qhd->bsd"),
    mesh=mesh, in_specs=(P(None, None, ("pod", "model"), None),
                         P(("pod", "model"), None, None)),
    out_specs=P(), check_vma=False)
np.testing.assert_allclose(np.asarray(jax.jit(fa)(o8, wo)),
                           np.einsum("bsqh,qhd->bsd", o8, wo),
                           rtol=1e-4, atol=1e-4)
print("collective_matmul attention-spec OK")

# --- rd_all_reduce chunked-path edge cases ---------------------------------
mesh8 = make_mesh((8,), ("pd",))


def run8(fn, xv):
    f = shard_map(fn, mesh=mesh8, in_specs=P("pd"), out_specs=P("pd"),
                  check_vma=False)
    return np.asarray(jax.jit(f)(xv))


x8 = rng.standard_normal((8, 7, 9)).astype(np.float32)   # 63 elems/shard
ref8 = run8(lambda v: lax.psum(v, "pd"), x8)
for chunks in (1, 2, 3, 5, 64, 1000):   # 63 % 3 != 0; 1000 > payload
    got = run8(lambda v: rd_all_reduce(v, "pd", chunks=chunks), x8)
    np.testing.assert_allclose(got, ref8, rtol=1e-5), chunks
print("rd_all_reduce chunk edge cases OK")

# non-power-of-two axis falls back to psum (with chunking requested too)
mesh3 = make_mesh((3,), ("m",))
x3 = rng.standard_normal((6, 5)).astype(np.float32)
f3 = shard_map(lambda v: rd_all_reduce(v, "m", chunks=4), mesh=mesh3,
               in_specs=P("m"), out_specs=P("m"), check_vma=False)
g3 = shard_map(lambda v: lax.psum(v, "m"), mesh=mesh3, in_specs=P("m"),
               out_specs=P("m"), check_vma=False)
np.testing.assert_allclose(jax.jit(f3)(x3), jax.jit(g3)(x3), rtol=1e-5)
print("rd_all_reduce non-pow2 fallback OK")

# --- sequence-parallel reduce-scatter variant ------------------------------
ctx_sp = ParallelCtx(tp_fast=("model",), tp_slow=("pod",), sp=("model",),
                     ar_strategy="hier_rd")
x_sp = rng.standard_normal((B, 8, F)).astype(np.float32)  # S=8 % 4 == 0


def run_sp(fn):
    f = shard_map(fn, mesh=mesh,
                  in_specs=(P(None, None, ("pod", "model")),
                            P(("pod", "model"), None)),
                  out_specs=P(None, "model", None), check_vma=False)
    return np.asarray(jax.jit(f)(x_sp, w))


rs_base = run_sp(lambda xs, ws: tp_reduce_scatter(
    jnp.einsum("bsf,fd->bsd", xs, ws), ctx_sp, dim=1))
rs_ovr = run_sp(lambda xs, ws: collective_matmul_reduce_scatter(
    xs, ws, ctx_sp.replace(overlap_matmul=True, overlap_chunks=4), dim=1))
np.testing.assert_allclose(rs_ovr, rs_base, rtol=1e-5, atol=1e-5)
print("collective_matmul_reduce_scatter parity OK")

# --- end-to-end: overlapped auto decode == flat decode ---------------------
from repro.models import ModelConfig, make_plan, init_params
from repro.parallel.steps import build_decode_step, build_prefill

cfg = ModelConfig(name="ovl-tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                  vocab_size=96, dtype=jnp.float32)
ap = make_plan(cfg, 8)
params = init_params(jax.random.PRNGKey(0), ap)
prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 96)
toks = {}
for name, ctx_d in [
    ("flat", ParallelCtx(tp_fast=("model",), tp_slow=("pod",),
                         ep=("model",), ar_strategy="flat")),
    ("auto+overlap", ParallelCtx(tp_fast=("model",), tp_slow=("pod",),
                                 ep=("model",), ar_strategy="auto",
                                 overlap_matmul=True, overlap_chunks=4)),
]:
    pre = build_prefill(ap, ctx_d, mesh, s_max=24)
    dec = build_decode_step(ap, ctx_d, mesh)
    nxt, cache = jax.jit(pre.fn)(params, prompts)
    seq = [np.asarray(nxt)]
    pos = jnp.full((4,), 8, jnp.int32)
    for i in range(6):
        nxt, cache = dec.jit()(params, cache, nxt, pos + i)
        seq.append(np.asarray(nxt))
    toks[name] = np.stack(seq)
assert np.array_equal(toks["flat"], toks["auto+overlap"]), \
    "overlapped auto decode must reproduce flat greedy tokens"
print("e2e overlapped auto decode parity OK")

# --- int8 weight dequant under TP x overlap ---------------------------------
# quantized decode (dequant_layer inside the scan) on the hierarchical
# recursive-doubling all-reduce: greedy tokens must not depend on the
# overlap knob in the weight-quant configuration either.
from repro.models.transformer import init_cache
from repro.parallel.quant import quantize_params

qparams = quantize_params(params)
qtoks = {}
for name, ov in (("plain", False), ("overlap", True)):
    ctx_q = ParallelCtx(tp_fast=("model",), tp_slow=("pod",), ep=("model",),
                        ar_strategy="hier_rd", overlap_matmul=ov,
                        overlap_chunks=4)
    dec_q = build_decode_step(ap, ctx_q, mesh, weight_quant=True)
    cache_q = shard_map(lambda: init_cache(ap, 4, 24, local=True),
                        mesh=mesh, in_specs=(),
                        out_specs=dec_q.in_specs[1], check_vma=False)()
    cur = jnp.full((4,), 7, jnp.int32)
    seq = []
    for i in range(6):
        cur, cache_q = dec_q.jit()(qparams, cache_q,
                                   cur, jnp.full((4,), i, jnp.int32))
        seq.append(np.asarray(cur))
    qtoks[name] = np.stack(seq)
assert np.array_equal(qtoks["plain"], qtoks["overlap"]), \
    "weight-quant decode must not depend on overlap"
print("weight-quant dequant under TP x overlap OK")

# --- fused Pallas GEMM+RD kernel (TPU interpret mode) ----------------------
from jax.experimental.pallas import tpu as pltpu
from repro.kernels.rd_allreduce.fused_matmul import collective_matmul_pallas
ctx_k = ParallelCtx(tp_fast=("model",), tp_slow=("pod",),
                    ar_strategy="hier_rd")
fkm = shard_map(
    lambda xs, ws: collective_matmul_pallas(
        xs, ws, ctx_k, spec="bsf,fd->bsd", chunks=2,
        interpret=pltpu.InterpretParams()),
    mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False)
np.testing.assert_allclose(np.asarray(jax.jit(fkm)(x, w)), ref,
                           rtol=1e-4, atol=1e-4)
print("fused pallas collective matmul OK")
print("overlap+autotune OK")
