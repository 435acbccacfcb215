"""Wire bytes of the TP collectives, read from the lowered HLO on a
(2 pod x 4 model) mesh of 8 host devices; nothing is executed.

- Sequence parallelism: each collective of the RS+AG decomposition moves
  at most half the wire bytes of the one fused (flat) all-reduce of the
  same residual message (DESIGN.md §10).
- Quantized wire: in the paper's 128 KB-2 MB window, the int8 / int4
  hierarchical all-reduce moves at least 1.9x / 3.5x fewer wire bytes
  than the bf16 path it replaces (packed payload plus bf16 group scales;
  exact factors 1.97x / 3.76x, DESIGN.md §12).
"""
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import (ParallelCtx, tp_all_gather, tp_all_reduce,
                        tp_reduce_scatter)
from repro.core.compat import make_mesh
from repro.launch.hlo_analysis import collective_bytes

KB = 1024
MB = 1024 * KB
mesh = make_mesh((2, 4), ("pod", "model"))
flat = ParallelCtx(tp_fast=("model",), tp_slow=("pod",), ar_strategy="flat")
rd = flat.replace(ar_strategy="hier_rd")


def wire(fn, x):
    """(total wire bytes, collective count) of ``fn`` lowered on x."""
    f = jax.jit(shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                          check_vma=False))
    st = collective_bytes(f.lower(x).as_text(dialect="hlo"), 8, 2)
    assert st.count > 0, "no collectives in the lowered module"
    return st.wire_ici_bytes + st.wire_dcn_bytes, st.count


for msg in (16 * KB, 64 * KB, 256 * KB, 1 * MB, 4 * MB, 16 * MB):
    x = jax.ShapeDtypeStruct((msg // 4,), jnp.float32)
    fused, n_fused = wire(lambda v: tp_all_reduce(v, flat), x)
    sp, n_sp = wire(lambda v: tp_all_gather(
        tp_reduce_scatter(v, flat, dim=0), flat, dim=0), x)
    ratio = (sp / n_sp) / (fused / n_fused)
    assert ratio <= 0.5 + 1e-6, (msg, ratio)
print("sp per-collective wire <= 1/2 fused OK")

for msg in (256 * KB, 1 * MB):
    x = jax.ShapeDtypeStruct((msg // 2,), jnp.bfloat16)
    fp, _ = wire(lambda v: tp_all_reduce(v, rd), x)
    for quant, floor in (("int8", 1.9), ("int4", 3.5)):
        q, _ = wire(lambda v, c=rd.replace(ar_quant=quant):
                    tp_all_reduce(v, c), x)
        assert fp / q >= floor, (msg, quant, fp / q)
        print(f"{msg // KB} KB {quant}: {fp / q:.2f}x fewer wire bytes")
print("quantized wire reduction OK")
print("wire bytes OK")
