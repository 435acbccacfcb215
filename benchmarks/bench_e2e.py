"""Paper Fig. 7 (+ Fig. 16): end-to-end NVRAR-vs-NCCL speedup for
decode-heavy batched inference across models and GPU counts, plus a REAL
numerical end-to-end run: the tiny engine generating with flat vs
hierarchical all-reduce strategies produces identical tokens (correctness of
the integration the speedups rely on)."""
from __future__ import annotations

from .common import emit


def simulated():
    from repro.inference.simulator import simulate_batch_latency, A100, GH200
    from repro.core.comm_model import PERLMUTTER, VISTA
    from repro.configs.llama3_paper import LLAMA31_70B, LLAMA31_405B

    for model, gpus in ((LLAMA31_70B, (8, 16, 32)),
                        (LLAMA31_405B, (32, 64, 128))):
        for npr in (8, 32):
            for n in gpus:
                t_n, _ = simulate_batch_latency(
                    model, A100, PERLMUTTER, n, scheme="tp",
                    ar_algo="nccl", prompt_len=1426, decode_len=3072,
                    n_prompts=npr)
                t_v, _ = simulate_batch_latency(
                    model, A100, PERLMUTTER, n, scheme="tp",
                    ar_algo="nvrar", prompt_len=1426, decode_len=3072,
                    n_prompts=npr)
                emit(f"fig7/{model.name}/P{npr}/gpus{n}", t_v * 1e6,
                     f"nccl_s={t_n:.1f};speedup={t_n/t_v:.2f}x")
    # Vista (Fig. 16): 1 GPU/node
    for n in (4, 8, 16):
        t_n, _ = simulate_batch_latency(
            LLAMA31_70B, GH200, VISTA, n, scheme="tp", ar_algo="nccl",
            prompt_len=1426, decode_len=3072, n_prompts=32)
        t_v, _ = simulate_batch_latency(
            LLAMA31_70B, GH200, VISTA, n, scheme="tp", ar_algo="nvrar",
            prompt_len=1426, decode_len=3072, n_prompts=32)
        emit(f"fig16/vista/llama70b/P32/gpus{n}", t_v * 1e6,
             f"nccl_s={t_n:.1f};speedup={t_n/t_v:.2f}x")


def real_integration():
    """Numerical equivalence of the AR strategies inside a real generate()
    loop (8 simulated devices; run via the dist harness when available)."""
    import jax
    if len(jax.devices()) < 8:
        emit("fig7/real_integration", 0.0, "skipped=needs_8_devices")
        return
    import numpy as np
    import jax.numpy as jnp
    from repro.core.compat import make_mesh
    from repro.core.pcontext import ParallelCtx
    from repro.models import ModelConfig, make_plan, init_params
    from repro.parallel.steps import build_decode_step, build_prefill
    cfg = ModelConfig(name="bench-tiny", family="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_ff=128, vocab_size=96, dtype=jnp.float32)
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
        for i in range(6):
            nxt, cache = dec.jit()(params, cache, nxt, pos + i)
            seq.append(np.asarray(nxt))
        toks[strat] = np.stack(seq)
    same = bool(np.array_equal(toks["flat"], toks["hier_rd"]))
    emit("fig7/real_integration_tokens_match", float(same),
         "flat_vs_hier_rd_identical_generations")
    assert same


def crossover_sweep(out_path: str = "BENCH_crossover.json"):
    """Decode-regime crossover table: for each (model d_model x batch)
    decode all-reduce message size, the modelled per-strategy latency on the
    tpu_v5e NetworkSpec and the ``ar_strategy="auto"`` dispatcher's pick.

    This is the table the paper's Sec. 4.3/5 crossover claim reduces to for
    our target topology (16-wide ICI fast axis x 2/4 DCN pods); device-free.
    """
    import json
    from repro.core import autotune
    from repro.core.comm_model import TPU_V5E, decode_allreduce_bytes

    rows = []
    for d_model in (2048, 4096, 8192, 16384):
        for batch in (1, 8, 32, 128):
            msg = decode_allreduce_bytes(batch, d_model)  # bf16
            for slow in (2, 4):
                fast = 16
                times = autotune.predict_times(msg, fast, slow, TPU_V5E)
                pick = autotune.analytic_choice(msg, fast, slow, TPU_V5E)
                rows.append({
                    "d_model": d_model, "batch": batch, "msg_bytes": msg,
                    "fast": fast, "slow": slow,
                    "pick": pick.strategy, "rd_chunks": pick.rd_chunks,
                    "t_us": {s: t * 1e6 for s, t in times.items()},
                })
                emit(f"crossover/H{d_model}_B{batch}_pods{slow}",
                     times[pick.strategy] * 1e6,
                     f"msg_kb={msg // 1024};pick={pick.strategy}")
    # prefill-regime companion table: for prompt-sized residual messages,
    # the modelled fused-AR vs RS+AG (sequence-parallel) times and the
    # seq_parallel="auto" pick — decode rows above stay fused, these flip
    # to SP once bandwidth dominates (DESIGN.md §10)
    sp_rows = []
    for d_model in (2048, 4096, 8192):
        for prompt in (512, 2048, 8192):
            msg = prompt * d_model * 2  # bf16
            for slow in (2, 4):
                t = autotune.predict_sp_times(msg, 16, slow, TPU_V5E)
                sp = bool(t["rs_ag"] < t["fused"])
                sp_rows.append({
                    "d_model": d_model, "prompt_tokens": prompt,
                    "msg_bytes": msg, "fast": 16, "slow": slow,
                    "fused_us": t["fused"] * 1e6,
                    "rs_ag_us": t["rs_ag"] * 1e6, "sp": sp,
                })
                emit(f"crossover/sp_H{d_model}_S{prompt}_pods{slow}",
                     t["rs_ag"] * 1e6,
                     f"fused_us={t['fused']*1e6:.1f};sp={sp}")
    with open(out_path, "w") as f:
        json.dump({"network": "tpu_v5e", "rows": rows,
                   "sp_rows": sp_rows}, f, indent=2, sort_keys=True)
    emit("crossover/json_written", float(len(rows)), out_path)
    return rows


def run():
    simulated()
    real_integration()


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sweep", action="store_true",
                    help="emit the decode crossover table "
                         "(BENCH_crossover.json); device-free")
    ap.add_argument("--out", default="BENCH_crossover.json")
    args = ap.parse_args(argv)
    if args.sweep:
        crossover_sweep(args.out)
    else:
        run()


if __name__ == "__main__":
    main()
