"""Benchmark harness — one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run``  prints ``name,us_per_call,
derived`` CSV rows.  Everything runs in this one process, so on a TPU host
it holds the chips once.  The multi-device parts (real sharded-integration
checks, the RD kernel run) use the devices JAX finds and print a
``skipped=`` row when there are too few; on the CPU, give them 8 host
devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Map to the paper:
    bench_scaling    -> Figs. 1, 2, 11 (strong scaling TP vs HP)
    bench_breakdown  -> Figs. 3, 8 (+ straggler sensitivity)
    bench_gemm       -> Table 4 (M-halving vs K-halving)
    bench_allreduce  -> Figs. 4, 6, 14, 15 (algorithm comparison)
    bench_chunks     -> Table 5 (chunk-size sensitivity)
    bench_e2e        -> Figs. 7, 16 (end-to-end NVRAR speedup)
    bench_trace      -> Figs. 9, 18 (trace serving throughput)
    bench_moe        -> Fig. 10 (MoE TP x EP)
    roofline_table   -> EXPERIMENTS.md §Roofline summary
"""
from __future__ import annotations


def main() -> None:
    from . import (bench_scaling, bench_breakdown, bench_gemm,
                   bench_allreduce, bench_chunks, bench_e2e, bench_trace,
                   bench_moe, roofline_table)
    print("name,us_per_call,derived")
    bench_scaling.run()
    bench_breakdown.run()
    bench_gemm.run()
    bench_allreduce.model_sweep()
    bench_allreduce.tpu_projection()
    bench_chunks.modelled_sweep()
    bench_e2e.simulated()
    bench_trace.simulated()
    bench_trace.real_scheduler()
    bench_moe.simulated()
    bench_e2e.real_integration()
    bench_moe.real_moe_integration()
    bench_chunks.kernel_structure()
    roofline_table.run()


if __name__ == "__main__":
    main()
