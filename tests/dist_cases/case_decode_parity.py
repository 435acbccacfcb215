"""Sharded prefill+decode produces identical greedy tokens to the local
model, across TP layouts including cross-pod TP with hierarchical RD."""
import numpy as np, jax, jax.numpy as jnp
from jax import lax
from repro.core.compat import make_mesh
from repro.models import ModelConfig, make_plan, init_params, init_cache, forward_lm, decode_step
from repro.core import LOCAL, ParallelCtx
from repro.parallel.steps import build_decode_step, build_prefill

mesh = make_mesh((2, 4), ("pod", "model"))

def tiny(family, **kw):
    base = dict(name=f"tiny-{family}", family=family, n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=96,
                dtype=jnp.float32)
    base.update(kw)
    return ModelConfig(**base)

key = jax.random.PRNGKey(0)
B, S = 4, 8

def parity(cfg, ctx, tp, label):
    ap1, apN = make_plan(cfg, 1), make_plan(cfg, tp)
    p1, pN = init_params(key, ap1), init_params(key, apN)
    tok = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    lg1, _, st1, _ = forward_lm(p1, tok, ap1, LOCAL, collect_state=True)
    c1 = init_cache(ap1, B, S + 4)
    if "k" in c1:
        c1["k"] = lax.dynamic_update_slice(c1["k"], st1["k"].astype(c1["k"].dtype), (0,)*5)
        c1["v"] = lax.dynamic_update_slice(c1["v"], st1["v"].astype(c1["v"].dtype), (0,)*5)
    for nm in ("conv", "ssm", "shift_tm", "shift_cm", "wkv"):
        if nm in c1: c1[nm] = st1[nm].astype(c1[nm].dtype)
    nxt1 = jnp.argmax(lg1[:, -1, :cfg.vocab_size], -1).astype(jnp.int32)
    toks1, pos = [nxt1], jnp.full((B,), S, jnp.int32)
    for i in range(3):
        lg, c1 = decode_step(p1, c1, toks1[-1], pos + i, ap1, LOCAL)
        toks1.append(jnp.argmax(lg[:, :cfg.vocab_size], -1).astype(jnp.int32))
    pre = build_prefill(apN, ctx, mesh, s_max=S + 4)
    dec = build_decode_step(apN, ctx, mesh)
    nxtN, cN = jax.jit(pre.fn)(pN, tok)
    toksN = [nxtN]
    for i in range(3):
        tN, cN = dec.jit()(pN, cN, toksN[-1], pos + i)
        toksN.append(tN)
    for a, b in zip(toks1, toksN):
        assert np.array_equal(np.asarray(a), np.asarray(b)), label
    print(label, "OK")

ctxA = ParallelCtx(tp_fast=("model",), dp=("pod",), ep=("model",), ar_strategy="flat")
ctxB = ParallelCtx(tp_fast=("model",), tp_slow=("pod",), ep=("model",), ar_strategy="hier_rd")
ctxC = ParallelCtx(tp_fast=("model",), tp_slow=("pod",), ep=("model",), ar_strategy="hier_rd_halving")
parity(tiny("dense"), ctxA, 4, "dense tp4+dp")
parity(tiny("dense"), ctxB, 8, "dense tp8 hier_rd")
parity(tiny("dense"), ctxC, 8, "dense tp8 hier_rd_halving")
parity(tiny("dense", n_heads=5, n_kv_heads=5, qkv_bias=True), ctxB, 8, "mha5 tp8")
parity(tiny("moe", n_experts=8, top_k=2, d_ff_expert=32, capacity_factor=8.0), ctxA, 4, "moe tp4")
parity(tiny("hybrid", d_inner=128, ssm_state=8, sliding_window=4), ctxA, 4, "hybrid tp4")
parity(tiny("ssm", d_model=128, rwkv_head_dim=32, decay_lora=8), ctxA, 4, "rwkv tp4")
parity(tiny("ssm", d_model=128, rwkv_head_dim=16, decay_lora=8), ctxB, 8, "rwkv tp8")
print("decode parity OK")
