"""Paper Figs. 4 & 6 (+ Appendix C.3): all-reduce algorithm comparison.

Four evidence channels (no real interconnect in this container):
1. alpha-beta model sweep — NCCL Ring/Tree vs NVRAR across message sizes and
   GPU counts on Perlmutter/Vista constants (the paper's own modelling
   frame, Eqs. 1-6);
2. HLO-structural measurement — lower the hierarchical vs flat strategies on
   the 512-chip multi-pod mesh with cross-pod TP and compare *slow-axis
   (DCN) collective payload bytes* from the lowered module: NVRAR's
   reduce-scatter shrinks the inter-node payload by G=16x;
3. the TPU-target projection with v5e ICI/DCN constants;
4. ``--sweep``: a REAL strategy x message-size latency grid measured on 8
   simulated host devices, cross-checked against the autotuned dispatcher's
   per-bucket pick (chosen-vs-best regret), persisted to
   ``BENCH_allreduce.json`` so the perf trajectory is tracked across PRs.
   The sweep also carries an **RS+AG column** (``sp_rows``): the
   sequence-parallel decomposition of the same residual message — measured
   pair latency, per-collective wire bytes from the lowered HLO (asserted
   <= half the fused single-collective all-reduce's), and the
   ``seq_parallel="auto"`` dispatcher's SP-vs-fused pick per size
   (DESIGN.md §10: prefill-sized messages decompose, decode-sized stay on
   the fused hierarchical-RD path).
   A **quantized-wire column** (``quant_rows``) measures the int8/int4
   compressed hierarchical all-reduce against the bf16 fp path at each
   size: per-module wire bytes from the lowered HLO (asserted >= 1.9x /
   3.5x smaller in the 128KB-2MB window — the packed payload plus bf16
   group scales), measured latency, and the deterministic
   ``ar_quant="auto"`` analytic level per bucket (quantized at >= 1
   bandwidth-bound size, fp at decode-sized messages; DESIGN.md §12).
"""
from __future__ import annotations

from .common import emit


KB = 1024
MB = 1024 * KB

# --sweep grid: decode-regime through clearly bandwidth-bound messages.
SWEEP_SIZES = (16 * KB, 64 * KB, 256 * KB, 1 * MB, 4 * MB, 16 * MB)
SWEEP_STRATEGIES = ("flat", "hier_ring", "hier_rd", "hier_rd_halving")


def model_sweep():
    from repro.core import comm_model as cm
    for net in (cm.PERLMUTTER, cm.VISTA):
        for msg_kb in (64, 128, 256, 512, 1024, 2048, 4096):
            for ngpu in (8, 16, 32, 64, 128):
                n_nodes = max(1, ngpu // net.gpus_per_node)
                g = min(ngpu, net.gpus_per_node)
                if n_nodes < 2:
                    continue
                algo, t_nccl = cm.nccl_model_best(msg_kb * KB, n_nodes, g,
                                                  net)
                t_nv = cm.t_nvrar(msg_kb * KB, n_nodes, g, net)
                emit(f"fig6/{net.name}/allreduce_{msg_kb}KB_{ngpu}gpu",
                     t_nv * 1e6,
                     f"nccl_{algo}_us={t_nccl*1e6:.1f};"
                     f"speedup={t_nccl/t_nv:.2f}x")


def tpu_projection():
    from repro.core import comm_model as cm
    net = cm.TPU_V5E
    for msg_kb in (128, 256, 1024):
        for pods in (2, 4, 8):
            t_ring = cm.t_ring_allreduce(msg_kb * KB, pods, 16, net)
            t_nv = cm.t_nvrar(msg_kb * KB, pods, 16, net)
            emit(f"tpu/allreduce_{msg_kb}KB_{pods}pods", t_nv * 1e6,
                 f"flat_ring_us={t_ring*1e6:.1f};"
                 f"speedup={t_ring/t_nv:.2f}x")


def hlo_structural():
    """DCN payload per decode step: flat vs hierarchical strategies, lowered
    on the 2x16x16 mesh with TP spanning the pod (DCN) axis."""
    import os
    if len(__import__("jax").devices()) < 512:
        emit("fig6/hlo_structural", 0.0, "skipped=needs_512_devices")
        return
    from repro.launch.mesh import make_production_mesh
    from repro.launch.input_specs import build_cell
    from repro.launch.hlo_analysis import collective_bytes
    mesh = make_production_mesh(multi_pod=True)
    res = {}
    for strat in ("flat", "hier_rd", "hier_rd_halving"):
        cell = build_cell("llama3.2-1b", "decode_32k", mesh,
                          ar_strategy=strat, cross_pod_tp=True)
        lowered = cell.lower()
        st = collective_bytes(lowered.as_text(dialect="hlo"), 512, 2)
        res[strat] = st
        emit(f"fig6/hlo/decode_dcn_bytes_{strat}", st.dcn_bytes,
             f"ici_bytes={st.ici_bytes};n_colls={st.count}")
    if res["flat"].dcn_bytes > 0:
        emit("fig6/hlo/dcn_reduction_hier_vs_flat",
             res["flat"].dcn_bytes / max(res["hier_rd"].dcn_bytes, 1),
             "per_layer_inter_payload_shrinks_by_G")


def measured_sweep(out_path: str = "BENCH_allreduce.json"):
    """Measure every strategy at every SWEEP_SIZES message on an 8-device
    (2 pod x 4 model) host mesh, record into an AutoTuner, and emit the
    strategy grid + the dispatcher's chosen-vs-best regret per size bucket.

    Requires >= 8 devices.
    """
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core.compat import make_mesh
    from repro.core import (tp_all_reduce, tp_reduce_scatter, tp_all_gather,
                            ParallelCtx, autotune)
    from repro.core import comm_model as cm
    from repro.launch.hlo_analysis import collective_bytes
    from .common import timeit

    if len(jax.devices()) < 8:
        emit("sweep/skipped", 0.0, "needs_8_devices")
        return None

    mesh = make_mesh((2, 4), ("pod", "model"))
    fast_n, slow_n = 4, 2

    def _shmap(fn):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=P(),
                                 out_specs=P(), check_vma=False))

    def _per_coll_wire(f, x):
        """Mean per-collective wire bytes of a lowered executable."""
        st = collective_bytes(f.lower(x).as_text(dialect="hlo"), 8, 2)
        assert st.count > 0, "no collectives in lowered module"
        return (st.wire_ici_bytes + st.wire_dcn_bytes) / st.count, st.count

    tuner = autotune.AutoTuner(cm.TPU_V5E)
    grid = []
    picks = []
    sp_rows = []
    quant_rows = []
    for msg_bytes in SWEEP_SIZES:
        n_elems = msg_bytes // 4  # f32 payload
        x = np.random.default_rng(0).standard_normal(n_elems) \
            .astype(np.float32)
        measured = {}
        for strat in SWEEP_STRATEGIES:
            ctx = ParallelCtx(tp_fast=("model",), tp_slow=("pod",),
                              ar_strategy=strat)
            # Replicated input: every device holds the FULL msg_bytes
            # partial, exactly like a TP decode partial sum — and exactly
            # how the runtime dispatcher (_resolve_auto) keys the lookup.
            f = jax.jit(shard_map(
                lambda v: tp_all_reduce(v, ctx, scatter_dim=-1),
                mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
            us = timeit(lambda: jax.block_until_ready(f(x)),
                        warmup=2, iters=5)
            measured[strat] = us
            # Record under every dtype the dispatcher queries: the byte
            # bucket already encodes the size, and what we measure here is
            # collective *structure*, which is dtype-agnostic — without
            # this, bf16 decode lookups (AutoTuner.choose's default) would
            # miss every measured entry.
            for dt in ("float32", "bfloat16", "float16"):
                tuner.record(msg_bytes, fast_n, slow_n, dt, strat,
                             us * 1e-6)
            grid.append({"msg_bytes": msg_bytes, "strategy": strat,
                         "us": us})
            emit(f"sweep/allreduce_{msg_bytes // KB}KB_{strat}", us,
                 f"devices=8;fast={fast_n};slow={slow_n}")
        analytic = tuner.choose(msg_bytes, fast_n, slow_n,
                                "float32").strategy
        best = min(measured, key=measured.get)
        regret = measured[analytic] / measured[best] - 1.0
        picks.append({"msg_bytes": msg_bytes, "analytic_pick": analytic,
                      "measured_best": best,
                      "analytic_us": measured[analytic],
                      "best_us": measured[best],
                      "regret": regret})
        emit(f"sweep/pick_{msg_bytes // KB}KB", measured[analytic],
             f"analytic={analytic};best={best};regret={regret:.3f}")

        # -- RS+AG column: the sequence-parallel decomposition ------------
        # Same residual message, issued as tp_reduce_scatter (ending the
        # row-parallel projection) + deferred tp_all_gather.  Latency is
        # measured with the shipped hier_rd slow phase; per-collective
        # wire bytes are read from the lowered HLO against the fused
        # single-collective (flat) all-reduce — the decomposition halves
        # what each collective moves (DESIGN.md §10).
        ctx_flat = ParallelCtx(tp_fast=("model",), tp_slow=("pod",),
                               ar_strategy="flat")
        ctx_rd = ctx_flat.replace(ar_strategy="hier_rd")
        f_fused = _shmap(lambda v: tp_all_reduce(v, ctx_flat,
                                                 scatter_dim=-1))
        def sp_pair(v, ctx=ctx_rd):
            return tp_all_gather(tp_reduce_scatter(v, ctx, dim=0), ctx,
                                 dim=0)
        f_sp = _shmap(sp_pair)
        f_sp_flat = _shmap(lambda v: sp_pair(v, ctx_flat))
        rs_ag_us = timeit(lambda: jax.block_until_ready(f_sp(x)),
                          warmup=2, iters=5)
        fused_pc, _ = _per_coll_wire(f_fused, x)
        sp_pc, sp_n = _per_coll_wire(f_sp_flat, x)
        auto_sp = tuner.choose_sp(msg_bytes, fast_n, slow_n, "float32")
        sp_rows.append({
            "msg_bytes": msg_bytes,
            "rs_ag_us": rs_ag_us,
            "fused_flat_us": measured["flat"],
            "auto_sp": auto_sp,
            "fused_pick": analytic,
            "fused_per_coll_wire_bytes": fused_pc,
            "rs_ag_per_coll_wire_bytes": sp_pc,
            "rs_ag_collectives": sp_n,
            "per_coll_ratio": sp_pc / fused_pc,
        })
        emit(f"sweep/rs_ag_{msg_bytes // KB}KB", rs_ag_us,
             f"auto_sp={auto_sp};per_coll_ratio={sp_pc / fused_pc:.3f}")

        # -- quantized-wire column: int8/int4 compressed all-reduce -------
        # The wire accounting runs against the bf16 payload (what decode
        # actually ships): a bf16 tensor of exactly msg_bytes through the
        # fp hierarchical-RD path vs the quantized one.  Wire bytes come
        # from the lowered HLO (packed int payload + bf16 group scales),
        # so the reduction factor is deterministic on any runner; the
        # measured latencies are recorded under the tuner's "auto"
        # namespace but only the analytic level is gated (CPU emulation
        # pays pack/unpack compute without real wire savings).
        xb = jnp.asarray(
            np.random.default_rng(1).standard_normal(msg_bytes // 2),
            jnp.bfloat16)
        q_wire = {}
        q_us = {}
        for quant in ("none", "int8", "int4"):
            ctx_q = ctx_rd.replace(ar_quant=quant)
            f_q = _shmap(lambda v, c=ctx_q: tp_all_reduce(v, c,
                                                          scatter_dim=-1))
            st = collective_bytes(f_q.lower(xb).as_text(dialect="hlo"),
                                  8, 2)
            assert st.count > 0
            q_wire[quant] = (st.wire_ici_bytes + st.wire_dcn_bytes,
                             st.count)
            q_us[quant] = timeit(lambda: jax.block_until_ready(f_q(xb)),
                                 warmup=2, iters=5)
            tuner.record(msg_bytes, fast_n, slow_n, "bfloat16", "hier_rd",
                         q_us[quant] * 1e-6, quant=quant, policy="auto")
        auto_q = autotune.analytic_quant_choice(
            msg_bytes, fast_n, slow_n, cm.TPU_V5E, "auto").quant
        for quant in ("int8", "int4"):
            red = q_wire["none"][0] / q_wire[quant][0]
            quant_rows.append({
                "msg_bytes": msg_bytes,
                "quant": quant,
                "wire_reduction": red,
                "q_wire_bytes": q_wire[quant][0],
                "fp_wire_bytes": q_wire["none"][0],
                "q_collectives": q_wire[quant][1],
                "q_us": q_us[quant],
                "fp_us": q_us["none"],
                "auto_bits": {"none": 0, "int8": 8, "int4": 4}[auto_q],
            })
            emit(f"sweep/quant_{msg_bytes // KB}KB_{quant}", q_us[quant],
                 f"wire_reduction={red:.2f}x;auto={auto_q}")
    # acceptance: each SP collective carries <= half the fused AR's wire
    # bytes, and the dispatcher splits the regimes — SP at prefill-sized
    # messages, fused hierarchical-RD at decode-sized ones.
    assert all(r["per_coll_ratio"] <= 0.5 + 1e-6 for r in sp_rows), sp_rows
    assert not sp_rows[0]["auto_sp"] and \
        sp_rows[0]["fused_pick"] == "hier_rd", sp_rows[0]
    assert all(r["auto_sp"] for r in sp_rows
               if r["msg_bytes"] >= 1 * MB), sp_rows
    # acceptance (quantized wire): the compressed payload beats the bf16
    # fp wire by >= 1.9x (int8) / 3.5x (int4) in the paper's 128KB-2MB
    # contended window (exact factors 1.97x / 3.76x: packed ints + bf16
    # group scales at GROUP_CAP granularity), and the deterministic
    # analytic ar_quant="auto" dispatch quantizes >= 1 bandwidth-bound
    # bucket while leaving decode-sized messages on the fp path.
    floors = {"int8": 1.9, "int4": 3.5}
    for r in quant_rows:
        if 128 * KB <= r["msg_bytes"] <= 2 * MB:
            assert r["wire_reduction"] >= floors[r["quant"]], r
    assert any(r["auto_bits"] for r in quant_rows), quant_rows
    assert all(r["auto_bits"] == 0 for r in quant_rows
               if r["msg_bytes"] <= 64 * KB), quant_rows
    # refine: measured winners overwrite the analytic seeds
    tuner.refine()
    doc = {
        "device_count": 8,
        "mesh": [2, 4],
        "topology": {"fast": fast_n, "slow": slow_n},
        "dtype": "float32",
        "note": ("latencies are CPU host-device emulation - relative "
                 "ordering tracks collective structure (message count / "
                 "payload), not real ICI/DCN wire time"),
        "grid": grid,
        "picks": picks,
        "sp_rows": sp_rows,
        "quant_rows": quant_rows,
        "tuned_table": tuner.to_json(),
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    emit("sweep/json_written", float(len(grid)), out_path)
    return doc


def run():
    model_sweep()
    tpu_projection()
    hlo_structural()


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sweep", action="store_true",
                    help="measure the strategy x message-size grid on the "
                         "8 devices JAX finds (on the CPU, start with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8)"
                         " and write BENCH_allreduce.json")
    ap.add_argument("--out", default="BENCH_allreduce.json")
    args = ap.parse_args(argv)
    if not args.sweep:
        run()
        return
    measured_sweep(args.out)


if __name__ == "__main__":
    main()
