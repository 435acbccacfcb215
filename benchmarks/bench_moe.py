"""Paper Fig. 10: MoE serving (Qwen3-235B-A22B-like) — NVRAR accelerates the
TP all-reduce of the non-MoE layers, orthogonal to EP.  Simulated trace
throughput for TP16-EP16 with NCCL vs NVRAR vs PP, plus a REAL numerical
check that the qwen3-moe smoke model produces identical generations under
flat vs hierarchical AR (EP + hierarchical TP compose correctly)."""
from __future__ import annotations

import dataclasses

import numpy as np

from .common import emit


def simulated():
    from repro.inference.simulator import simulate_trace, A100
    from repro.core.comm_model import PERLMUTTER
    from repro.models.common import ModelConfig

    qwen3_235b = ModelConfig(
        name="qwen3-235b-a22b", family="moe",
        n_layers=94, d_model=4096, n_heads=64, n_kv_heads=4, head_dim=128,
        d_ff=1536, vocab_size=151936, n_experts=128, top_k=8,
        d_ff_expert=1536)

    rng = np.random.default_rng(0)
    n = 500
    li = np.maximum(2, rng.lognormal(np.log(600), 0.6, n)).astype(int)
    lo = np.maximum(1, rng.lognormal(np.log(250), 0.6, n)).astype(int)
    arr = np.cumsum(rng.gamma(0.5, scale=1.0 / (10.0 * 0.5), size=n))
    for conc in (32, 128):
        res = {}
        for label, scheme, algo in (("tp16_ep16_nccl", "tp", "nccl"),
                                    ("tp16_ep16_nvrar", "tp", "nvrar"),
                                    ("pp4", "hp", "nccl")):
            out = simulate_trace(qwen3_235b, A100, PERLMUTTER, 16,
                                 scheme=scheme, ar_algo=algo,
                                 arrivals=arr, in_lens=li, out_lens=lo,
                                 concurrency=conc)
            res[label] = out["throughput_tok_s"]
            emit(f"fig10/C{conc}/{label}", out["makespan_s"] * 1e6,
                 f"throughput_tok_s={out['throughput_tok_s']:.1f}")
        emit(f"fig10/C{conc}/nvrar_gain",
             res["tp16_ep16_nvrar"] / max(res["tp16_ep16_nccl"], 1e-9),
             "moe_tp_ar_acceleration")


def real_moe_integration():
    import jax
    if len(jax.devices()) < 8:
        emit("fig10/real_moe", 0.0, "skipped=needs_8_devices")
        return
    import jax.numpy as jnp
    from repro.core.compat import make_mesh
    from repro.core.pcontext import ParallelCtx
    from repro.models import ModelConfig, make_plan, init_params
    from repro.parallel.steps import build_decode_step, build_prefill
    cfg = ModelConfig(name="moe-tiny", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=32,
                      vocab_size=96, n_experts=8, top_k=2, d_ff_expert=32,
                      capacity_factor=8.0, dtype=jnp.float32)
    mesh = make_mesh((2, 4), ("pod", "model"))
    toks = {}
    for strat in ("flat", "hier_rd"):
        ctx = ParallelCtx(tp_fast=("model",), tp_slow=("pod",),
                          ep=("model",), ar_strategy=strat)
        ap = make_plan(cfg, 8)
        params = init_params(jax.random.PRNGKey(0), ap)
        pre = build_prefill(ap, ctx, mesh, s_max=24)
        dec = build_decode_step(ap, ctx, mesh)
        prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 96)
        nxt, cache = jax.jit(pre.fn)(params, prompts)
        seq = [np.asarray(nxt)]
        pos = jnp.full((4,), 8, jnp.int32)
        for i in range(4):
            nxt, cache = dec.jit()(params, cache, nxt, pos + i)
            seq.append(np.asarray(nxt))
        toks[strat] = np.stack(seq)
    same = bool(np.array_equal(toks["flat"], toks["hier_rd"]))
    emit("fig10/real_moe_tokens_match", float(same), "ep_x_hier_tp")
    assert same


def run():
    simulated()
    real_moe_integration()


if __name__ == "__main__":
    run()
