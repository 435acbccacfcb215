"""Parallel execution context.

``ParallelCtx`` carries the mesh-axis wiring for a jitted step function.  All
model code takes a ctx and calls the collective helpers in
:mod:`repro.core.hierarchical`; with an empty ctx (no axes) every collective
degenerates to the identity, so the same model code runs single-device (CPU
tests, smoke tests) and under ``jax.shard_map`` on a production mesh.

Axis roles
----------
- ``tp_fast``: tensor-parallel axes on the fast interconnect (ICI).  The
  paper's "intra-node" level.
- ``tp_slow``: tensor-parallel axes on the slow interconnect (DCN).  The
  paper's "inter-node" level; non-empty only for cross-pod TP deployments.
- ``dp``:     pure batch-parallel axes (gradients reduced across them).
- ``fsdp``:   weight-sharding axes; weights are all-gathered per layer on the
  forward pass (ZeRO-3 style), which AD transposes into gradient
  reduce-scatters.
- ``ep``:     expert-parallel axes for MoE layers (usually == tp_fast).
- ``sp``:     sequence-parallel axes (activations sequence-sharded between
  blocks; usually == tp_fast).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

AxisNames = Tuple[str, ...]

AR_STRATEGIES = ("flat", "hier_ring", "hier_rd", "hier_rd_halving", "auto")

SEQ_PARALLEL_MODES = ("off", "on", "auto")

# Quantized-collective levels for the TP all-reduce / RS+AG family.
# "none" keeps full-precision wire; "int8"/"int4" force that level at every
# call site; "auto" lets the autotuner pick {none, int8, int4} per call site
# (requires ar_strategy="auto" so the same trace-time dispatch hook fires).
AR_QUANT_LEVELS = ("none", "int8", "int4")
AR_QUANT_MODES = AR_QUANT_LEVELS + ("auto",)


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    tp_fast: AxisNames = ()
    tp_slow: AxisNames = ()
    dp: AxisNames = ()
    fsdp: AxisNames = ()
    ep: AxisNames = ()
    sp: AxisNames = ()
    # All-reduce strategy for TP partial sums (the paper's subject):
    #   flat             - single XLA all-reduce over all TP axes (NCCL baseline)
    #   hier_ring        - RS(fast) + psum(slow, XLA ring) + AG(fast)
    #   hier_rd          - RS(fast) + recursive doubling(slow) + AG(fast)  [NVRAR]
    #   hier_rd_halving  - RS(fast) + recursive halving/doubling(slow) + AG(fast)
    #   auto             - per-call-site dispatch on (message bytes, topology,
    #                      dtype) via repro.core.autotune (resolved at trace
    #                      time; see DESIGN.md §Overlap-and-autotune)
    ar_strategy: str = "flat"
    # Gradient cross-pod reduction strategy ("flat" | "rd").
    grad_reduce_strategy: str = "rd"
    # Chunk count for pipelined slow-axis exchanges (paper Sec. 4.2.1 analogue).
    rd_chunks: int = 1
    # Quantized collective level for tp_all_reduce / tp_reduce_scatter /
    # tp_all_gather: "none" | "int8" | "int4" | "auto".  int8/int4 carry
    # nibble/byte-packed payloads + per-group bf16 scales on the wire
    # (Flash-Communication-style low-bit comm); "auto" lets the AutoTuner
    # pick {none, int8, int4} per call site alongside the strategy (needs
    # ar_strategy="auto").  Error feedback for the lossy levels rides in
    # the decode cache (see DESIGN.md §12).
    ar_quant: str = "none"
    # Overlapped collective-matmul: route row-parallel output projections
    # (attention wo / MLP down-proj) through repro.core.overlap so chunk q's
    # all-reduce pipelines against chunk q+1's GEMM (Flash-Communication
    # style comm/compute fusion; see DESIGN.md §Overlap-and-autotune).
    overlap_matmul: bool = False
    # Output-feature chunk count for the overlapped path (1 disables
    # chunking even when overlap_matmul is set).
    overlap_chunks: int = 4
    # Sequence-parallel prefill (Megatron-SP residual layout): the residual
    # stream stays sequence-sharded over tp_fast between sublayers — the
    # row-parallel projections (attention wo / MLP down) end in
    # tp_reduce_scatter on the sequence dim, norms run on sequence shards,
    # and tp_all_gather restores full sequence only where QKV / up-proj
    # need it.  "off" keeps the fused per-residual all-reduce, "on" forces
    # the RS+AG decomposition wherever the sequence divides tp_fast, and
    # "auto" dispatches per call site on message size via the autotuner's
    # SP table (decode steps never decompose — their one-token messages
    # live in the latency-bound regime; see DESIGN.md §10).
    seq_parallel: str = "off"

    def __post_init__(self):
        if self.ar_strategy not in AR_STRATEGIES:
            raise ValueError(f"unknown ar_strategy {self.ar_strategy!r}")
        if self.seq_parallel not in SEQ_PARALLEL_MODES:
            raise ValueError(
                f"unknown seq_parallel mode {self.seq_parallel!r}")
        if self.ar_quant not in AR_QUANT_MODES:
            raise ValueError(f"unknown ar_quant mode {self.ar_quant!r}")
        if self.ar_quant == "auto" and self.ar_strategy != "auto":
            raise ValueError(
                "ar_quant='auto' requires ar_strategy='auto' (quant level "
                "is picked by the same trace-time autotune dispatch); got "
                f"ar_strategy={self.ar_strategy!r}")

    # -- derived -----------------------------------------------------------
    @property
    def tp_axes(self) -> AxisNames:
        return self.tp_slow + self.tp_fast

    @property
    def has_tp(self) -> bool:
        return bool(self.tp_axes)

    @property
    def batch_axes(self) -> AxisNames:
        return self.dp

    def replace(self, **kw) -> "ParallelCtx":
        return dataclasses.replace(self, **kw)


# A fully-local context: every collective is the identity.
LOCAL = ParallelCtx()


def single_pod_ctx(ar_strategy: str = "flat", **kw) -> ParallelCtx:
    """Default wiring for the (16,16) = ("data","model") mesh."""
    return ParallelCtx(tp_fast=("model",), dp=("data",), fsdp=("data",),
                       ep=("model",), sp=("model",), ar_strategy=ar_strategy,
                       **kw)


def multi_pod_ctx(ar_strategy: str = "flat", cross_pod_tp: bool = False,
                  **kw) -> ParallelCtx:
    """Wiring for the (2,16,16) = ("pod","data","model") mesh.

    ``cross_pod_tp=True`` reproduces the paper's headline scenario: the TP
    group spans the slow interconnect, so the per-layer all-reduce crosses
    DCN and the hierarchical strategies apply verbatim.
    """
    if cross_pod_tp:
        return ParallelCtx(tp_fast=("model",), tp_slow=("pod",),
                           dp=("data",), fsdp=("data",), ep=("model",),
                           sp=("model",), ar_strategy=ar_strategy, **kw)
    return ParallelCtx(tp_fast=("model",), dp=("pod", "data"),
                       fsdp=("data",), ep=("model",), sp=("model",),
                       ar_strategy=ar_strategy, **kw)


__all__ = ["ParallelCtx", "LOCAL", "single_pod_ctx", "multi_pod_ctx",
           "AR_STRATEGIES", "SEQ_PARALLEL_MODES", "AR_QUANT_LEVELS",
           "AR_QUANT_MODES"]
