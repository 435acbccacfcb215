"""Core: the paper's contribution — hierarchical all-reduce for multi-node
(multi-pod) LLM inference/training, plus its alpha-beta performance models."""
from .pcontext import ParallelCtx, LOCAL, single_pod_ctx, multi_pod_ctx
from .hierarchical import (
    rd_all_reduce, rd_halving_all_reduce, tp_all_reduce, tp_reduce_scatter,
    tp_all_gather, grad_cross_pod_reduce, dp_psum_mean, axes_size,
)
from .overlap import collective_matmul, collective_matmul_reduce_scatter
from . import comm_model
from . import autotune

__all__ = [
    "ParallelCtx", "LOCAL", "single_pod_ctx", "multi_pod_ctx",
    "rd_all_reduce", "rd_halving_all_reduce", "tp_all_reduce",
    "tp_reduce_scatter", "tp_all_gather",
    "grad_cross_pod_reduce", "dp_psum_mean", "axes_size", "comm_model",
    "collective_matmul", "collective_matmul_reduce_scatter", "autotune",
]
