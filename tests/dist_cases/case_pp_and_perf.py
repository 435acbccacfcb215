"""Hybrid parallelism (GPipe PP x TP) parity + perf-feature parity on 8
simulated devices: quantized all-gather, SP prefill, cross-pod int8 RD."""
import numpy as np, jax, jax.numpy as jnp
from repro.core.compat import make_mesh
from repro.models import ModelConfig, make_plan, init_params, forward_lm
from repro.core import LOCAL, ParallelCtx
from repro.parallel.pp import build_pp_forward
from repro.parallel.steps import build_prefill, build_decode_step

cfg = ModelConfig(name="t", family="dense", n_layers=4, d_model=64, n_heads=4,
                  n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=96,
                  dtype=jnp.float32)
mesh = make_mesh((2, 4), ("pod", "model"))
tok = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 96)

# --- PP x TP (the paper's HP scheme) vs local ------------------------------
ctx = ParallelCtx(tp_fast=("model",), ep=("model",))
ap = make_plan(cfg, 4)
params = init_params(jax.random.PRNGKey(0), ap)
fn, _ = build_pp_forward(ap, ctx, mesh, stage_axis="pod", microbatches=4)
logits_pp = np.asarray(jax.jit(fn)(params, tok), np.float32)
ap1 = make_plan(cfg, 1)
p1 = init_params(jax.random.PRNGKey(0), ap1)
ref = np.asarray(forward_lm(p1, tok, ap1, LOCAL)[0], np.float32)
assert np.abs(logits_pp - ref).max() / np.abs(ref).max() < 2e-3
print("pp_parity OK")

# --- SP prefill + quantized AG + int8 KV + ring, all at once ---------------
cfgs = ModelConfig(name="s", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                   vocab_size=96, sliding_window=8, dtype=jnp.float32)
aps = make_plan(cfgs, 4)
ps = init_params(jax.random.PRNGKey(0), aps)
base_ctx = ParallelCtx(tp_fast=("model",), dp=("pod",), ep=("model",),
                       sp=("model",))
toks = {}
for name, ctx2, kw in [
    ("plain", base_ctx, {}),
    ("sp", base_ctx, {"sp": True}),
    ("q8ag", base_ctx.replace(quant_ag=True), {}),
]:
    pre = build_prefill(aps, ctx2, mesh, s_max=32, **kw)
    nxt, cache = jax.jit(pre.fn)(ps, tok)
    dec = build_decode_step(aps, ctx2, mesh)
    seq = [np.asarray(nxt)]
    pos = jnp.full((8,), 16, jnp.int32)
    for i in range(4):
        nxt, cache = dec.jit()(ps, cache, nxt, pos + i)
        seq.append(np.asarray(nxt))
    toks[name] = np.stack(seq)
assert np.array_equal(toks["plain"], toks["sp"]), "sp prefill parity"
# quant_ag is intentionally lossy (int8 + per-128 scales): require a high
# greedy-token agreement rate rather than bit equality
q8_match = np.mean(toks["plain"] == toks["q8ag"])
assert q8_match >= 0.8, f"quant_ag match rate {q8_match}"
print(f"sp parity exact; quant_ag match rate {q8_match:.2f} OK")

# --- int8 KV + ring-window decode vs bf16 full cache -----------------------
ctx3 = base_ctx
for variant, kw in [("kv_int8", {"kv_quant": True}),
                    ("ring", {"window_cache": True})]:
    pre = build_prefill(aps, ctx3, mesh, s_max=32)
    dec_ref = build_decode_step(aps, ctx3, mesh)
    dec_var = build_decode_step(aps, ctx3, mesh, **kw)
    # both decode from scratch (pos 0..) so ring/prefill seeding isn't needed
    from repro.models.transformer import init_cache
    from repro.parallel import sharding as shd
    cache_r = init_cache(aps, 8, 32, local=False)
    cache_v = init_cache(aps, 8, 32, local=False, **{
        "kv_quant": kw.get("kv_quant", False),
        "window_cache": kw.get("window_cache", False)})
    cur_r = cur_v = jnp.arange(8, dtype=jnp.int32)
    outs_r, outs_v = [], []
    for i in range(10):
        lr, cache_r = dec_ref.fn(ps, cache_r, cur_r, jnp.full((8,), i, jnp.int32))
        lv, cache_v = dec_var.fn(ps, cache_v, cur_v, jnp.full((8,), i, jnp.int32))
        cur_r, cur_v = lr, lv
        outs_r.append(np.asarray(lr)); outs_v.append(np.asarray(lv))
    match = np.mean(np.stack(outs_r) == np.stack(outs_v))
    thresh = 1.0 if variant == "ring" else 0.8  # int8 may flip rare ties
    assert match >= thresh, (variant, match)
    print(f"{variant} decode token match rate: {match:.2f} OK")

# --- int8 WEIGHTS decode parity --------------------------------------------
from repro.parallel.quant import quantize_params
dec_w = build_decode_step(aps, ctx3, mesh, weight_quant=True)
qparams = quantize_params(ps)
from repro.models.transformer import init_cache as _ic
c_r = _ic(aps, 8, 32, local=False)
c_w = _ic(aps, 8, 32, local=False)
dec_r2 = build_decode_step(aps, ctx3, mesh)
cur_r = cur_w = jnp.arange(8, dtype=jnp.int32)
m = t = 0
for i in range(8):
    cur_r, c_r = dec_r2.fn(ps, c_r, cur_r, jnp.full((8,), i, jnp.int32))
    cur_w, c_w = dec_w.fn(qparams, c_w, cur_w, jnp.full((8,), i, jnp.int32))
    m += int(np.sum(np.asarray(cur_r) == np.asarray(cur_w))); t += 8
assert m / t >= 0.8, f"weight-quant match {m}/{t}"
print(f"weight_quant decode match {m}/{t} OK")
print("pp+perf case OK")
