"""Device-free tests for the message-size-aware all-reduce autotuner
(repro.core.autotune): analytic dispatch, crossover behavior, measurement
refinement, and JSON persistence."""
import json
import os
import warnings

import pytest

from repro.core import autotune as at
from repro.core import comm_model as cm
from repro.core.pcontext import ParallelCtx

KB = 1024
MB = 1024 * KB


def test_auto_is_a_valid_ctx_strategy():
    ctx = ParallelCtx(tp_fast=("model",), tp_slow=("pod",),
                      ar_strategy="auto")
    assert ctx.ar_strategy == "auto"
    with pytest.raises(ValueError):
        ParallelCtx(ar_strategy="definitely_not_a_strategy")


def test_predict_times_positive_and_monotone():
    for net in (cm.TPU_V5E, cm.PERLMUTTER):
        t_small = at.predict_times(64 * KB, 16, 4, net)
        t_big = at.predict_times(64 * MB, 16, 4, net)
        for s in at.DISPATCHABLE:
            assert t_small[s] > 0
            assert t_big[s] > t_small[s], (net.name, s)


def test_auto_selects_different_strategies_small_vs_large_tpu_v5e():
    """Acceptance: on the tpu_v5e NetworkSpec the dispatcher must flip
    strategies between a 64 KB and a 64 MB payload (the paper's crossover:
    recursive doubling in the latency regime, bandwidth-optimal algorithms
    once the wire dominates)."""
    small = at.analytic_choice(64 * KB, 16, 4, cm.TPU_V5E)
    large = at.analytic_choice(64 * MB, 16, 4, cm.TPU_V5E)
    assert small.strategy != large.strategy, (small, large)
    # and the small-message pick is the paper's NVRAR-style RD
    assert small.strategy == "hier_rd"
    times_small = at.predict_times(64 * KB, 16, 4, cm.TPU_V5E)
    times_large = at.predict_times(64 * MB, 16, 4, cm.TPU_V5E)
    assert times_small[small.strategy] == min(
        times_small[s] for s in at.DISPATCHABLE)
    assert times_large[large.strategy] == min(
        times_large[s] for s in at.DISPATCHABLE)


def test_single_level_topology_degenerates():
    choice = at.analytic_choice(256 * KB, 8, 1, cm.TPU_V5E)
    assert choice.strategy in at.DISPATCHABLE  # no slow axis: any is fine
    times = at.predict_times(256 * KB, 8, 1, cm.TPU_V5E)
    assert len(set(times.values())) == 1  # all equal: one-level reduction


def test_rd_chunks_kick_in_for_large_rd_messages():
    # At slow=2, full-exchange RD matches every rival's bandwidth with the
    # fewest latency steps, so hier_rd wins at any size — and once the
    # slow-phase shard (msg/fast) crosses the chunk threshold the pick
    # pipelines the exchange (paper Sec. 4.2.1).
    choice = at.analytic_choice(16 * MB, 16, 2, cm.TPU_V5E)
    assert choice.strategy == "hier_rd"
    assert choice.rd_chunks > 1
    # tiny messages never chunk
    tiny = at.analytic_choice(32 * KB, 16, 4, cm.TPU_V5E)
    assert tiny.rd_chunks == 1


def test_tuner_lookup_caches_and_buckets():
    t = at.AutoTuner(cm.TPU_V5E)
    a = t.choose(100 * KB, 16, 4)
    b = t.choose(100 * KB + 1, 16, 4)  # same pow2 bucket
    assert a == b
    assert len(t.table) == 1
    t.choose(100 * KB, 16, 2)  # different topology -> new entry
    assert len(t.table) == 2


def test_measurement_refinement_overrides_analytic():
    t = at.AutoTuner(cm.TPU_V5E)
    assert t.choose(64 * KB, 16, 4).strategy == "hier_rd"
    t.record(64 * KB, 16, 4, "bfloat16", "hier_ring", 1.0e-6)
    t.record(64 * KB, 16, 4, "bfloat16", "hier_rd", 9.0e-6)
    assert t.refine() == 1
    assert t.choose(64 * KB, 16, 4).strategy == "hier_ring"


def test_save_load_roundtrip(tmp_path):
    t = at.AutoTuner(cm.TPU_V5E)
    t.choose(64 * KB, 16, 4)
    t.choose(64 * MB, 16, 4)
    p = os.path.join(tmp_path, "ar_table.json")
    t.save(p)
    doc = json.load(open(p))
    assert doc["net"] == "tpu_v5e" and len(doc["table"]) == 2
    t2 = at.AutoTuner.load(p)
    assert t2.table == t.table


def test_save_load_roundtrip_lossy(tmp_path):
    """A table saved before the int8 slow exchange was removed still
    loads: each entry's ``"compress_slow": false`` and the top-level
    ``"allow_lossy"`` flag are ignored, so every such entry is kept and
    dispatches as before; an entry with ``"compress_slow": true`` names
    the removed path and is dropped with a warning."""
    old = {
        "allow_lossy": False,
        "net": "tpu_v5e",
        "sp_table": {"b20/f4/s2/bfloat16": True},
        "table": {
            "b16/f4/s2/bfloat16": {"compress_slow": False, "quant": "none",
                                   "rd_chunks": 1, "strategy": "hier_rd"},
            "b20/f4/s2/bfloat16": {"compress_slow": False, "quant": "none",
                                   "rd_chunks": 1, "strategy": "hier_rd"},
            "b20/f4/s2/bfloat16:qauto": {"compress_slow": False,
                                         "quant": "int4", "rd_chunks": 1,
                                         "strategy": "hier_rd"},
            "b24/f4/s2/bfloat16": {"compress_slow": False, "quant": "none",
                                   "rd_chunks": 8, "strategy": "hier_rd"},
        },
        "version": 1,
    }
    p = os.path.join(tmp_path, "old_table.json")
    with open(p, "w") as f:
        json.dump(old, f)
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # a clean old table: no warning
        t = at.AutoTuner.load(p)
    want = {k: at.ARChoice(strategy=v["strategy"], rd_chunks=v["rd_chunks"],
                           quant=v["quant"])
            for k, v in old["table"].items()}
    assert t.table == want
    assert t.sp_table == old["sp_table"]
    # the loaded entries dispatch exactly as the stored ones
    ctx = ParallelCtx(tp_fast=("model",), tp_slow=("pod",),
                      ar_strategy="auto", ar_quant="auto")
    prev = at.install(t)
    try:
        r = at.resolve(ctx, 1 * MB, 4, 2, "bfloat16")
        assert (r.ar_strategy, r.rd_chunks, r.ar_quant) == \
            ("hier_rd", 1, "int4")
        r = at.resolve(ctx.replace(ar_quant="none"), 16 * MB, 4, 2,
                       "bfloat16")
        assert (r.ar_strategy, r.rd_chunks) == ("hier_rd", 8)
    finally:
        at.install(prev)
    # what the loaded tuner saves again carries neither old field
    doc = t.to_json()
    assert set(doc) == {"version", "net", "table", "sp_table"}
    assert all(set(v) == {"strategy", "rd_chunks", "quant"}
               for v in doc["table"].values())
    # an entry that selected the int8 slow exchange is dropped, the rest kept
    old["table"]["b28/f16/s4/bfloat16"] = {
        "compress_slow": True, "quant": "none", "rd_chunks": 1,
        "strategy": "hier_rd"}
    with open(p, "w") as f:
        json.dump(old, f)
    with pytest.warns(RuntimeWarning, match="dropped 1 "):
        t2 = at.AutoTuner.load(p)
    assert t2.table == want


def test_install_and_resolve_roundtrip():
    prev = at.install(at.AutoTuner(cm.TPU_V5E))
    try:
        ctx = ParallelCtx(tp_fast=("model",), tp_slow=("pod",),
                          ar_strategy="auto")
        r_small = at.resolve(ctx, 64 * KB, 16, 4, "bfloat16")
        r_large = at.resolve(ctx, 64 * MB, 16, 4, "bfloat16")
        assert r_small.ar_strategy != "auto"
        assert r_large.ar_strategy != "auto"
        assert r_small.ar_strategy != r_large.ar_strategy
        # the rest of the ctx is untouched
        assert r_small.tp_fast == ctx.tp_fast
        assert r_small.overlap_matmul == ctx.overlap_matmul
    finally:
        at.install(prev)


def test_install_from_path_env(tmp_path, monkeypatch):
    t = at.AutoTuner(cm.PERLMUTTER)
    t.choose(1 * MB, 4, 8)
    p = os.path.join(tmp_path, "tbl.json")
    t.save(p)
    prev = at.active()
    try:
        monkeypatch.setenv("REPRO_AR_TABLE", p)
        installed = at.install_from_path(None)
        assert installed.net.name == "perlmutter"
    finally:
        at.install(prev)


def test_key_parse_key_roundtrip():
    """_parse_key returns the bucket's upper bound (bucket_bytes), never
    the original message size — and re-keying the parsed tuple must be
    the identity (the invariant refine() relies on)."""
    for msg in (1, 300, 64 * KB, 100 * KB, 100 * KB + 1, 64 * MB):
        key = at._key(msg, 16, 4, "bfloat16")
        bucket_bytes, fast, slow, dtype = at._parse_key(key)
        assert (fast, slow, dtype) == (16, 4, "bfloat16")
        assert bucket_bytes >= min(msg, 256)       # bucket floor is 2**8
        assert bucket_bytes >= msg or msg <= 256
        assert bucket_bytes < 2 * max(msg, 256)    # tight upper bound
        assert at._key(bucket_bytes, fast, slow, dtype) == key
    # exact powers of two are their own bucket bound
    assert at._parse_key(at._key(256 * KB, 8, 2, "float32"))[0] == 256 * KB


def test_refine_chunks_use_bucket_bytes():
    """refine() recomputes rd_chunks from the bucket bound, so a measured
    hier_rd winner at a large bucket pipelines its slow exchange."""
    t = at.AutoTuner(cm.TPU_V5E)
    t.record(16 * MB, 16, 2, "float32", "hier_rd", 1.0e-6)
    t.record(16 * MB, 16, 2, "float32", "hier_ring", 9.0e-6)
    assert t.refine() >= 1
    choice = t.choose(16 * MB, 16, 2, "float32")
    assert choice.strategy == "hier_rd"
    assert choice.rd_chunks == at._rd_chunks_for(
        at._parse_key(at._key(16 * MB, 16, 2, "float32"))[0], 16)
    assert choice.rd_chunks > 1


def test_sp_dispatch_crossover_tpu_v5e():
    """seq_parallel='auto' acceptance: decode-sized messages stay on the
    fused (hierarchical-RD) path, prefill-sized messages decompose into
    RS+AG — on both the bench mesh topology (fast=4, slow=2) and the
    production frame (fast=16, slow=4)."""
    for fast, slow in ((4, 2), (16, 4)):
        assert not at.analytic_sp_choice(16 * KB, fast, slow, cm.TPU_V5E)
        assert at.analytic_sp_choice(1 * MB, fast, slow, cm.TPU_V5E)
        assert at.analytic_sp_choice(16 * MB, fast, slow, cm.TPU_V5E)
    # the fused pick SP is compared against at decode sizes is NVRAR
    assert at.analytic_choice(16 * KB, 16, 4, cm.TPU_V5E).strategy \
        == "hier_rd"
    # no fast axes -> nothing to decompose
    assert not at.analytic_sp_choice(16 * MB, 1, 4, cm.TPU_V5E)
    t = at.predict_sp_times(1 * MB, 16, 4, cm.TPU_V5E)
    assert t["fused"] > 0 and t["rs_ag"] > 0


def test_sp_table_persistence_and_lookup_log(tmp_path):
    t = at.AutoTuner(cm.TPU_V5E)
    assert not t.choose_sp(16 * KB, 4, 2)
    assert t.choose_sp(4 * MB, 4, 2)
    assert t.choose_sp(4 * MB + 1, 4, 2) == t.choose_sp(4 * MB, 4, 2)
    assert len(t.sp_table) == 3          # two buckets + the 4MB+1 bucket
    assert len(t.sp_lookup_buckets()) == len(t.sp_table)
    p = os.path.join(tmp_path, "sp_table.json")
    t.save(p)
    doc = json.load(open(p))
    assert doc["sp_table"] == {k: bool(v) for k, v in t.sp_table.items()}
    t2 = at.AutoTuner.load(p)
    assert t2.sp_table == t.sp_table
    # a persisted entry overrides the analytic seed
    key = at._key(16 * KB, 4, 2, "bfloat16")
    t2.sp_table[key] = True
    assert t2.choose_sp(16 * KB, 4, 2) is True


def test_seq_parallel_mode_validation():
    for mode in ("off", "on", "auto"):
        assert ParallelCtx(seq_parallel=mode).seq_parallel == mode
    with pytest.raises(ValueError):
        ParallelCtx(seq_parallel="maybe")


def test_rd_chunk_count_is_interior():
    """The pipelined slow exchange's chunk count (paper Table 5: the
    optimum lies between one chunk and many) starts at 1 for decode-sized
    messages, grows with the message, and stops at ``_MAX_RD_CHUNKS``
    far below the count the threshold alone would give."""
    sizes = [16 * KB * 4 ** i for i in range(10)]
    chunks = [at._rd_chunks_for(s, 4) for s in sizes]
    assert chunks[0] == 1
    assert chunks == sorted(chunks)
    assert 1 < max(chunks) == at._MAX_RD_CHUNKS
    huge = 1 << 34
    assert at._rd_chunks_for(huge, 1) == at._MAX_RD_CHUNKS \
        < huge // at._CHUNK_THRESHOLD_BYTES


def test_quant_auto_ladder_on_a_2x4_mesh():
    """``ar_quant="auto"`` on the (2 pod x 4 model) topology: messages of
    64 KB and less keep the full-precision wire on the NVRAR path, and at
    least one bandwidth-bound size up to 16 MB quantizes."""
    sizes = (16 * KB, 64 * KB, 256 * KB, 1 * MB, 4 * MB, 16 * MB)
    picks = {s: at.analytic_quant_choice(s, 4, 2, cm.TPU_V5E, "auto")
             for s in sizes}
    for s in sizes:
        if s <= 64 * KB:
            assert picks[s].quant == "none", (s, picks[s])
            assert picks[s] == at.analytic_choice(s, 4, 2, cm.TPU_V5E)
    assert picks[16 * KB].strategy == "hier_rd"
    assert any(p.quant != "none" for p in picks.values()), picks
