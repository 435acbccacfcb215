"""Ahead-of-time compiles for a described TPU v5e 2x2, with no chip
attached: the served decode step at codeqwen1.5-7b widths on one chip and
at tp=4, the admission-chunk steps and the model's named scopes in them,
Mistral-Large-2407's tp=4 step and chunks at its published widths, and
the Pallas kernels.  The chip's compiler runs here, so what it
refuses (a tiling, a VMEM budget, a program that does not fit) fails in
this file at no chip time.  Nothing runs: results and times need a chip.

The topology is described inside module-scoped fixtures only, never at
import time (one process at a time may load the TPU library), and the
persistent compilation cache is off around these compiles (an entry
written for a described chip cannot be read back without one).
"""
from __future__ import annotations

import dataclasses
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.core.pcontext import LOCAL, ParallelCtx
from repro.models.transformer import init_cache, init_params, make_plan
from repro.parallel.steps import build_serve_step
from repro.parallel.topology import mesh_and_ctx

V5E_HBM = 16 * 2**30
SLOTS, S_MAX, BLOCK = 8, 2048, 16
N_BLOCKS = SLOTS * (S_MAX // BLOCK) + 1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed (jax[cpu])
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        cc.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def meshes(topo):
    devs = np.asarray(topo.devices[:4])
    return {"4": Mesh(devs, ("model",), axis_types=(AxisType.Auto,)),
            "2x2": Mesh(devs.reshape(2, 2), ("pod", "model"),
                        axis_types=(AxisType.Auto,) * 2)}


def _codeqwen(n_layers: int, tp: int):
    cfg = dataclasses.replace(get_config("codeqwen1.5-7b"), n_layers=n_layers)
    return make_plan(cfg, tp)


def _serve_args(ap, *, local: bool, slots: int = SLOTS):
    params = jax.eval_shape(lambda k: init_params(k, ap),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: init_cache(
        ap, slots, S_MAX, local=local, block_size=BLOCK,
        n_blocks=slots * (S_MAX // BLOCK) + 1))
    i32 = jax.ShapeDtypeStruct((slots,), jnp.int32)
    state = {"tokens": i32, "positions": i32, "remaining": i32,
             "active": jax.ShapeDtypeStruct((slots,), jnp.bool_),
             "rng": jax.ShapeDtypeStruct((slots, 2), jnp.uint32),
             "sample_idx": i32}
    return params, cache, state


def _placed(tree, shardings):
    """ShapeDtypeStructs of ``tree`` carrying ``shardings`` (one sharding
    for every leaf, or a pytree prefix of PartitionSpecs on a mesh)."""
    if isinstance(shardings, SingleDeviceSharding):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=shardings), tree)
    mesh, specs = shardings
    return jax.tree.map(
        lambda spec, s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        specs, tree, is_leaf=lambda x: isinstance(x, P))


def _bytes_on_device(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def test_one_chip_serve_step_fits(one_chip):
    """The scanned one-chip decode step at codeqwen widths (2 layers)."""
    ap = _codeqwen(2, 1)
    step = build_serve_step(ap, LOCAL, None, s_max=S_MAX, slots=SLOTS,
                            block_size=BLOCK, n_blocks=N_BLOCKS)
    args = [_placed(a, one_chip) for a in _serve_args(ap, local=True)]
    compiled = step.jit().lower(*args).compile()
    used = _bytes_on_device(compiled)
    # 2 layers (0.93 GB) + embedding and head (1.51 GB) + their K/V pool
    assert 2.4e9 < used < V5E_HBM, used


CHUNK = 256
KV_SCOPES = {"kv_carry", "kv_write", "kv_gather", "attention"}
MOVES_NOTHING = ("parameter", "get-tuple-element", "tuple", "bitcast")


def _one_chip_texts(one_chip) -> dict:
    """Compiled text of the one-chip decode step and of the middle- and
    final-chunk admission executables (codeqwen widths, 2 layers)."""
    from repro.parallel.steps import build_admit_chunk_step
    ap = _codeqwen(2, 1)
    params, cache, state = [_placed(a, one_chip)
                            for a in _serve_args(ap, local=True)]
    kw = dict(s_max=S_MAX, slots=SLOTS, block_size=BLOCK, n_blocks=N_BLOCKS)
    tok = jax.ShapeDtypeStruct((1, CHUNK), jnp.int32, sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    chunk = (params, cache, tok, tok, i32, i32, rng)
    jobs = {"step": (build_serve_step(ap, LOCAL, None, **kw),
                     (params, cache, state)),
            "admit_mid": (build_admit_chunk_step(
                ap, LOCAL, None, chunk=CHUNK, sample=False, **kw), chunk),
            "admit_final": (build_admit_chunk_step(
                ap, LOCAL, None, chunk=CHUNK, **kw), chunk)}
    return {k: st.jit().lower(*a).compile().as_text()
            for k, (st, a) in jobs.items()}


@pytest.fixture(scope="module")
def one_chip_texts(one_chip):
    return _one_chip_texts(one_chip)


def _instructions(text: str):
    """(name, dtype, dims, opcode, fused) of each array-valued instruction,
    ``fused`` when it lies inside a fused computation."""
    fused = False
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")):
            fused = line.startswith("%fused_computation")
            continue
        m = re.match(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if m:
            yield m.groups() + (fused,)


def _top_level(text: str):
    """(name, dims, opcode) of each instruction outside fused
    computations: the ops a profiler trace names."""
    for name, _, dims, op, fused in _instructions(text):
        if not fused:
            yield name, dims, op


@pytest.mark.parametrize("which", ["step", "admit_mid", "admit_final"])
def test_one_chip_steps_name_their_cache_ops(one_chip_texts, which):
    """Every op of the chip's executable that makes a layer's K/V pool,
    the stacked pool or the gathered logical view carries a cache or
    attention scope; the model's other scopes are there too."""
    from repro.launch.hlo_analysis import op_scopes
    text = one_chip_texts[which]
    scopes = op_scopes(text)
    nb, U, hd = N_BLOCKS, 32, 128
    shapes = {f"2,{nb},{BLOCK},{U},{hd}", f"{nb},{BLOCK},{U},{hd}",
              f"{2 * nb},{BLOCK},{U},{hd}",
              f"{SLOTS * S_MAX // BLOCK},{BLOCK},{U},{hd}",
              f"{S_MAX // BLOCK},{BLOCK},{U},{hd}"}
    seen = [(n, op) for n, dims, op in _top_level(text) if dims in shapes
            and op not in MOVES_NOTHING]
    # the chunks write K and V and gather their slot's row; the decode
    # step only writes, its kernel reading the blocks in place
    assert len(seen) >= (2 if which == "step" else 4), seen
    assert all(scopes.get(n) in KV_SCOPES for n, _ in seen), \
        [(n, op, scopes.get(n)) for n, op in seen]
    want = KV_SCOPES | {"qkv", "o_proj", "mlp", "norm", "embed"}
    if which != "admit_mid":
        want |= {"head", "sample"}
    if which == "step":
        assert "kv_gather" not in set(scopes.values())
        want -= {"kv_gather"}
    assert want <= set(scopes.values()), want - set(scopes.values())


@pytest.mark.parametrize("which", ["step", "admit_mid", "admit_final"])
def test_one_chip_steps_copy_no_layer_pool(one_chip_texts, which):
    """The layers address their blocks inside the stacked paged pool: no
    instruction makes one layer's pool, and ``kv_carry`` holds only the
    s32 offset of each layer's block table and the bitcasts that view the
    stacked pools as one."""
    text = one_chip_texts[which]
    layer_pool = f"{N_BLOCKS},{BLOCK},32,128"
    made = [(n, op) for n, _, dims, op, _ in _instructions(text)
            if dims == layer_pool and op not in MOVES_NOTHING]
    assert not made, made
    from repro.launch.hlo_analysis import op_scopes
    carry = {n for n, s in op_scopes(text).items() if s == "kv_carry"}
    kinds = {(n, dt, op) for n, dt, _, op, _ in _instructions(text)
             if n in carry}
    assert any(dt == "s32" for _, dt, _ in kinds), kinds
    assert all(dt == "s32" or op == "bitcast" for _, dt, op in kinds), kinds


def test_one_chip_step_temp_drops_a_layer_pool(one_chip, sliced_layers):
    """The decode step's scratch memory, against the same step with each
    layer's pools sliced out and written back: at least one layer's K and
    V pool less."""
    ap = _codeqwen(2, 1)
    args = [_placed(a, one_chip) for a in _serve_args(ap, local=True)]

    def temp():
        step = build_serve_step(ap, LOCAL, None, s_max=S_MAX, slots=SLOTS,
                                block_size=BLOCK, n_blocks=N_BLOCKS)
        compiled = step.jit().lower(*args).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    got = temp()
    with sliced_layers():
        ref = temp()
    layer_kv = 2 * N_BLOCKS * BLOCK * 32 * 128 * 2     # K and V, bf16
    assert ref - got >= layer_kv, (ref, got)


def test_scopes_leave_the_one_chip_steps_unchanged(one_chip, one_chip_texts,
                                                   monkeypatch):
    """Compiled for the chip without the named scopes, each executable is
    the same program, and every op a trace names keeps its name."""
    import contextlib
    from repro.launch.hlo_analysis import program_text
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _one_chip_texts(one_chip)
    for k, text in one_chip_texts.items():
        assert program_text(text, renumber=True) \
            == program_text(plain[k], renumber=True), k
        assert list(_top_level(text)) == list(_top_level(plain[k])), k


def test_tp4_serve_step_on_pod_model_mesh(meshes, sliced_layers):
    """The tp=4 decode step on the (pod=2, model=2) mesh with hier_rd:
    weights and cache split four ways, the RD exchange a collective-
    permute.  The paged pools, split on their heads, reach each layer
    with no collective more than each layer's slice did."""
    from repro.launch.hlo_analysis import collective_bytes
    mesh = meshes["2x2"]
    _, ctx, _ = mesh_and_ctx(4, 2, ar_strategy="hier_rd",
                             devices=list(mesh.devices.flat))
    ap = _codeqwen(2, 4)

    def compile_step():
        step = build_serve_step(ap, ctx, mesh, s_max=S_MAX, slots=SLOTS,
                                block_size=BLOCK, n_blocks=N_BLOCKS)
        args = [_placed(a, (mesh, spec)) for a, spec in
                zip(_serve_args(ap, local=False), step.in_specs)]
        return step.jit().lower(*args).compile()

    compiled = compile_step()
    used = _bytes_on_device(compiled)
    # a quarter of the one-chip footprint, plus replicated norms/biases
    assert 0.5e9 < used < 1.2e9, used
    text = compiled.as_text()
    assert "collective-permute" in text
    with sliced_layers():
        ref = compile_step().as_text()
    got, want = collective_bytes(text, 4, 2), collective_bytes(ref, 4, 2)
    assert (got.count, got.by_kind) == (want.count, want.by_kind)


MISTRAL_LAYERS, MISTRAL_SLOTS, MISTRAL_D = 16, 32, 12288
V5E_COMPILER_HBM = 15.75 * 2**30       # what the v5e compiler lets one chip use
AR_BYTES = {"step": MISTRAL_SLOTS * MISTRAL_D * 2,      # 786,432 B, bf16
            "admit_mid": CHUNK * MISTRAL_D * 2,         # 6,291,456 B
            "admit_final": CHUNK * MISTRAL_D * 2}


@pytest.fixture(scope="module")
def mistral_tp4(meshes):
    """The tp=4 (one pod, flat all-reduce) decode step and middle- and
    final-chunk executables at Mistral-Large-2407 widths, as the
    benchmark serves them: 16 layers, 32 slots x 2048, blocks of 16."""
    from repro.parallel.steps import build_admit_chunk_step
    mesh = meshes["4"]
    _, ctx, _ = mesh_and_ctx(4, 1, ar_strategy="flat",
                             devices=list(mesh.devices.flat))
    cfg = dataclasses.replace(get_config("mistral-large-123b"),
                              n_layers=MISTRAL_LAYERS)
    ap = make_plan(cfg, 4)
    n_blocks = MISTRAL_SLOTS * (S_MAX // BLOCK) + 1
    kw = dict(s_max=S_MAX, slots=MISTRAL_SLOTS, block_size=BLOCK,
              n_blocks=n_blocks)
    step = build_serve_step(ap, ctx, mesh, **kw)
    params, cache, state = [
        _placed(a, (mesh, spec)) for a, spec in
        zip(_serve_args(ap, local=False, slots=MISTRAL_SLOTS), step.in_specs)]
    every = NamedSharding(mesh, P())
    tok = jax.ShapeDtypeStruct((1, CHUNK), jnp.int32, sharding=every)
    s32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=every)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=every)
    chunk = (params, cache, tok, tok, s32, s32, rng)
    jobs = {"step": (step, (params, cache, state)),
            "admit_mid": (build_admit_chunk_step(
                ap, ctx, mesh, chunk=CHUNK, sample=False, **kw), chunk),
            "admit_final": (build_admit_chunk_step(
                ap, ctx, mesh, chunk=CHUNK, **kw), chunk)}
    return {k: st.jit().lower(*a).compile() for k, (st, a) in jobs.items()}


def _computations(text: str):
    """{computation name: its instruction lines} of a compiled text."""
    out, name = {}, None
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")):
            name = line.split()[1] if line.startswith("ENTRY") \
                else line.split()[0]
            out[name.lstrip("%")] = []
        elif name is not None and line.startswith("  "):
            out[name.lstrip("%")].append(line)
    return out


def _all_reduces_per_run(text: str):
    """(instruction, bytes, times it runs per run of the executable) of
    each all-reduce: once, or once a layer inside the layer loop, whose
    trip count is the constant its condition compares with."""
    from repro.launch.hlo_analysis import collective_bytes
    comps = _computations(text)
    loop = re.search(r"while\(.*condition=%?([\w.\-]+), body=%?([\w.\-]+)",
                     text)
    cond, body = loop.groups()
    trips = [int(c) for line in comps[cond]
             for c in re.findall(r"constant\((\d+)\)", line)]
    assert len(trips) == 1, comps[cond]
    for comp, lines in comps.items():
        for line in lines:
            if re.search(r" all-reduce(-start)?\(", line):
                name = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) =", line).group(1)
                nbytes = collective_bytes(line, 4).by_kind["all-reduce"]
                yield name, nbytes, trips[0] if comp == body else 1


@pytest.mark.parametrize("which", ["step", "admit_mid", "admit_final"])
def test_mistral_tp4_executables_fit_one_chip(mistral_tp4, which):
    """Each chip holds a quarter of the weights (11.48 GB; the middle
    chunk leaves the output head, 0.2 GB, out of its arguments), its
    quarter of the paged pool (1.07 GB) and the executable's scratch,
    within what the v5e compiler allows."""
    used = _bytes_on_device(mistral_tp4[which])
    assert 12.3e9 < used < V5E_COMPILER_HBM, used


@pytest.mark.parametrize("which", ["step", "admit_mid", "admit_final"])
def test_mistral_tp4_all_reduces_and_their_scope(mistral_tp4, which):
    """Two all-reduces of the residual stream a layer (after attention
    and after the MLP) and one after the embedding lookup over the split
    vocabulary, each of 32 tokens x 12288 x bf16 in a decode step and 256
    x 12288 x bf16 in an admission chunk, every one in the
    `tp_all_reduce` scope that `allreduce_ms.decode` reads.  Sampling's
    own small all-reduces are not counted."""
    from repro.launch.hlo_analysis import op_scopes
    text = mistral_tp4[which].as_text()
    scopes = op_scopes(text)
    big = [(n, runs) for n, nbytes, runs in _all_reduces_per_run(text)
           if nbytes == AR_BYTES[which]]
    assert sum(runs for _, runs in big) == 2 * MISTRAL_LAYERS + 1, big
    assert {scopes.get(n) for n, _ in big} == {"tp_all_reduce"}, \
        [(n, scopes.get(n)) for n, _ in big]


@pytest.mark.parametrize("nbytes", [1024, 64 * 2**10, 2**20],
                         ids=["1KiB", "64KiB", "1MiB"])
@pytest.mark.parametrize("mesh_name,axis", [("4", "model"), ("2x2", "pod")])
def test_rd_all_reduce_kernel_compiles(meshes, mesh_name, axis, nbytes):
    """``rd_all_reduce_kernel_call`` through its wrapper, which lays a
    bf16 message of ``nbytes`` per device out as whole tiles per chunk."""
    from repro.kernels.rd_allreduce import rd_all_reduce_pallas
    mesh = meshes[mesh_name]
    every = P(mesh.axis_names)
    f = shard_map(lambda v: rd_all_reduce_pallas(v, axis, n_chunks=4),
                  mesh=mesh, in_specs=every, out_specs=every,
                  check_vma=False)
    x = jax.ShapeDtypeStruct((mesh.size * nbytes // 2,), jnp.bfloat16,
                             sharding=NamedSharding(mesh, every))
    text = jax.jit(f).lower(x).compile().as_text()
    assert "tpu_custom_call" in text


def test_fused_matmul_rd_kernel_compiles(meshes):
    """The fused GEMM + slow-axis RD kernel on the (pod, model) mesh."""
    from repro.kernels.rd_allreduce.fused_matmul import (
        collective_matmul_pallas)
    mesh = meshes["2x2"]
    ctx = ParallelCtx(tp_fast=("model",), tp_slow=("pod",),
                      ar_strategy="hier_rd")
    B, F, D = 8, 4096, 4096      # F: sharded contraction dim
    f = shard_map(lambda x, w: collective_matmul_pallas(
        x, w, ctx, spec="bsf,fd->bsd", chunks=4),
        mesh=mesh, in_specs=(P(None, None, ("pod", "model")),
                             P(("pod", "model"), None)),
        out_specs=P(), check_vma=False)
    x = jax.ShapeDtypeStruct((B, 1, F), jnp.bfloat16, sharding=NamedSharding(
        mesh, P(None, None, ("pod", "model"))))
    w = jax.ShapeDtypeStruct((F, D), jnp.bfloat16, sharding=NamedSharding(
        mesh, P(("pod", "model"), None)))
    text = jax.jit(f).lower(x, w).compile().as_text()
    assert "tpu_custom_call" in text


def test_paged_decode_attention_compiles(one_chip):
    """The paged decode-attention kernel over the cells' cache geometry
    (blocks of 16, s_max 2048): 64 slots of codeqwen's 32 query and 4 KV
    heads on one chip, and 32 slots of Mistral's 24 query and 2 KV heads
    a chip at tp=4, each over a stacked pool of 16 layers."""
    from repro.kernels.decode_attention import paged_decode_attention
    hd = 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for slots, Hq, U in ((64, 32, 4), (32, 24, 2)):
        n_blocks = 16 * (slots * (S_MAX // BLOCK) + 1)
        q = sds((slots, Hq, hd), jnp.bfloat16)
        kv = sds((n_blocks, BLOCK, U, hd), jnp.bfloat16)
        tbl = sds((slots, S_MAX // BLOCK), jnp.int32)
        lens = sds((slots,), jnp.int32)
        text = jax.jit(paged_decode_attention).lower(
            q, kv, kv, tbl, lens).compile().as_text()
        assert "tpu_custom_call" in text


CELL_SLOTS = 64


@pytest.fixture(scope="module")
def codeqwen_cell_step(one_chip):
    """The one-chip decode step at the codeqwen cell's geometry (4 KV
    heads, 64 slots x 2048, blocks of 16), 2 of its 16 layers."""
    cfg = dataclasses.replace(get_config("codeqwen1.5-7b"), n_layers=2,
                              n_kv_heads=4)
    ap = make_plan(cfg, 1)
    step = build_serve_step(ap, LOCAL, None, s_max=S_MAX, slots=CELL_SLOTS,
                            block_size=BLOCK,
                            n_blocks=CELL_SLOTS * (S_MAX // BLOCK) + 1)
    args = [_placed(a, one_chip)
            for a in _serve_args(ap, local=True, slots=CELL_SLOTS)]
    return step.jit().lower(*args).compile().as_text()


@pytest.mark.parametrize("cell", ["codeqwen_1chip", "mistral_tp4"])
def test_served_decode_step_reads_the_pool_in_place(request, cell):
    """The served decode step at each decode cell's geometry reads K/V
    through the paged kernel: the stacked pools go to its
    ``tpu_custom_call`` as they lie, no instruction makes the gathered
    view (``(slots*max_blocks, bs, U, hd)`` or ``(slots, s_max, U,
    hd)``), and no instruction on the pool's shape copies or transposes
    it: the only ones that make it are the ``kv_write`` scatters."""
    from repro.launch.hlo_analysis import op_scopes
    if cell == "codeqwen_1chip":
        text = request.getfixturevalue("codeqwen_cell_step")
        slots, U, layers = CELL_SLOTS, 4, 2
    else:
        text = request.getfixturevalue("mistral_tp4")["step"].as_text()
        slots, U, layers = MISTRAL_SLOTS, 2, MISTRAL_LAYERS
    mb, hd = S_MAX // BLOCK, 128
    nb = slots * mb + 1
    pool = f"{layers * nb},{BLOCK},{U},{hd}"
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "custom-call(" in line]
    assert len(kernels) == 1, kernels
    assert kernels[0].count(f"bf16[{pool}]") == 2, kernels[0][:400]
    gathered = {f"{slots * mb},{BLOCK},{U},{hd}", f"{slots},{S_MAX},{U},{hd}"}
    made = [(n, op) for n, _, dims, op, _ in _instructions(text)
            if dims in gathered]
    assert not made, made
    scopes = op_scopes(text)
    on_pool = [(n, op, scopes.get(n)) for n, _, dims, op, _ in
               _instructions(text)
               if dims in (pool, f"{layers},{nb},{BLOCK},{U},{hd}")
               and op not in MOVES_NOTHING]
    assert on_pool, "no instruction writes the pool"
    assert all(op in ("fusion", "scatter") and sc == "kv_write"
               for _, op, sc in on_pool), on_pool
