"""The reduction from a profiler trace to per-layer numbers, on a
synthetic two-chip trace whose answers are known, and on a short trace
recorded on a TPU v5e (if present)."""
import pathlib

import numpy as np
import pytest

from bench_helpers import BENCH, harness

import trace_reduce

# chip 0: one `step` run over [1000, 6000] ns holding a fusion [1000, 3000]
# and an all-reduce [4000, 5000]; chip 1: one op [1000, 2000].  The host
# opens the window [500, 9500], a tick [600, 3500] and a wait [5200, 9400].
SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 5000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 11 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 12 offset_ps: 3000000 duration_ps: 1000000 } }
  event_metadata { key: 10 value { id: 10 name: "jit_step(7)" } }
  event_metadata { key: 11 value { id: 11 name: "fusion.1" } }
  event_metadata { key: 12 value { id: 12 name: "all-reduce.3" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 11 offset_ps: 0 duration_ps: 1000000 } }
  event_metadata { key: 11 value { id: 11 name: "fusion.1" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 600000 duration_ps: 2900000 }
    events { metadata_id: 3 offset_ps: 5200000 duration_ps: 4200000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.tick" } }
  event_metadata { key: 3 value { id: 3 name: "bench.wait" } } }
"""
RECORDED = BENCH / "tests" / "data" / "decode_v5e.xplane.pb.gz"


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData
    return trace_reduce.Trace(ProfileData.from_text_proto(SYNTHETIC))


def test_busy_union_and_window(synthetic):
    assert synthetic.window_s == pytest.approx(9000e-9)
    # chip 0 busy 3000 ns, chip 1 busy 1000 ns: mean 2000 ns
    assert synthetic.busy_s() == pytest.approx(2000e-9)


def test_modules_ops_and_collectives(synthetic):
    assert synthetic.module_time(0) == {"step": (5000.0, 1)}
    assert synthetic.top_ops(10) == [["fusion.1", pytest.approx(2e-6)],
                                     ["all-reduce.3", pytest.approx(1e-6)]]
    chip = synthetic.chips[0]
    assert chip.ops_inside("step", trace_reduce.re.compile("^all-reduce")) \
        == 1000.0


def test_idle_gaps_named_by_host_span(synthetic):
    assert synthetic.longest_gaps(10) == [
        ["wait", pytest.approx(4500e-9)], ["tick", pytest.approx(1000e-9)],
        ["tick", pytest.approx(500e-9)]]


def test_readers_on_the_synthetic_trace(synthetic):
    run = harness.Run(cell=None, seconds=1.0, setup_s=0.0, token_times={},
                      arrivals={}, chips=2, peaks={}, trace=synthetic)
    assert harness.reader("idle_share.decode")(run) \
        == pytest.approx(100 * (1 - 2000 / 9000))
    assert harness.reader("decode_step_ms")(run) == pytest.approx(5e-3)
    assert harness.reader("prefill_chunk_ms")(run) is None


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace():
    """A traced window of the one-chip decode cell on a TPU v5e: the
    decode step is found, busy time lies inside the window, and the
    longest idle gaps are named by the benchmark's spans."""
    tr = trace_reduce.load(str(RECORDED))
    assert len(tr.chips) == 1
    mods = tr.module_time(0)
    assert mods["step"][1] > 0
    assert 0 < tr.busy_s() < tr.window_s
    gaps = tr.longest_gaps(10)
    assert gaps and all(name != "no span" for name, _ in gaps)
    assert sum(s for _, s in tr.top_ops(10)) <= tr.busy_s() * 1.0001
