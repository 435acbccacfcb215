"""Sharded train step: loss parity with the local model + learning + RD /
int8-RD cross-pod gradient strategies."""
import numpy as np, jax, jax.numpy as jnp
from repro.core.compat import make_mesh
from repro.models import ModelConfig, make_plan, init_params, forward_lm
from repro.models.layers import sharded_xent
from repro.core import LOCAL, ParallelCtx
from repro.parallel.steps import build_train_step
from repro.training import adamw_init

def tiny(family, **kw):
    base = dict(name=f"tiny-{family}", family=family, n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=96,
                dtype=jnp.float32)
    base.update(kw)
    return ModelConfig(**base)

key = jax.random.PRNGKey(0)
B, S = 8, 16
tok = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 96)
lab = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, 96)
batch = {"tokens": tok, "labels": lab}

def run(cfg, mesh_shape, axes, ctx, tp, mb, label):
    mesh = make_mesh(mesh_shape, axes)
    ap = make_plan(cfg, tp)
    params = init_params(key, ap)
    opt = adamw_init(params)
    built = build_train_step(ap, ctx, mesh, microbatches=mb, base_lr=1e-2, warmup=1)
    step = built.jit()
    ap1 = make_plan(cfg, 1)
    p1 = init_params(key, ap1)
    lg, aux, _, _ = forward_lm(p1, tok, ap1, LOCAL)
    ref = float(sharded_xent(lg, lab, LOCAL, ap1.vocab_pad, cfg.vocab_size))
    if cfg.is_moe: ref += cfg.router_aux_coef * float(aux)
    losses = []
    for i in range(4):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    tol = 2e-2 if cfg.is_moe else 2e-3
    assert abs(losses[0] - ref) < tol, (label, losses[0], ref)
    assert losses[-1] < losses[0], (label, losses)
    assert float(m["skipped"]) == 0.0
    print(label, "OK", losses[0], "->", losses[-1])

ctx1 = ParallelCtx(tp_fast=("model",), dp=("data",), fsdp=("data",), ep=("model",), sp=("model",))
run(tiny("dense"), (2, 4), ("data", "model"), ctx1, 4, 2, "dense fsdp+sp+mb2")
ctx2 = ParallelCtx(tp_fast=("model",), dp=("pod", "data"), fsdp=("data",),
                   ep=("model",), sp=("model",), grad_reduce_strategy="rd")
run(tiny("dense"), (2, 2, 2), ("pod", "data", "model"), ctx2, 2, 1, "multipod rd")
run(tiny("moe", n_experts=8, top_k=2, d_ff_expert=32, capacity_factor=8.0),
    (2, 4), ("data", "model"), ctx1, 4, 2, "moe fsdp+sp")
run(tiny("hybrid", d_inner=128, ssm_state=8), (2, 4), ("data", "model"), ctx1, 4, 1, "hybrid")
run(tiny("ssm", d_model=128, rwkv_head_dim=32, decay_lora=8), (2, 4),
    ("data", "model"), ctx1, 4, 1, "rwkv")
print("train parity OK")
