"""Device time by the program's scopes and idle time by the program's
spans (`scopes.py`), and the per-layer readers built on them, on a
synthetic trace whose answers are known."""
import pytest

from bench_helpers import harness, tiny_cell

import scopes
import trace_reduce

# chip 0: a `step` run [1000, 6000] holding fusion.1 [1000, 3000], fusion.2
# [3000, 4000] and copy.3 [4000, 4500]; two `admit_chunk` runs, [7000,
# 8000] holding fusion.7, and [8200, 9200] holding fusion.7 [8200, 8700]
# and fusion.9 [8700, 9200].  The host opens the window [500, 9500], a
# tick [600, 6200] holding the program's tick [650, 6150], and a step
# [900, 6100] holding the program's step [950, 6050] and its readback
# [4400, 6000] (keywords in the name, as older JAX writes them).
SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 20 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 20 offset_ps: 7200000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 11 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 12 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 13 offset_ps: 3000000 duration_ps: 500000 }
    events { metadata_id: 17 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 17 offset_ps: 7200000 duration_ps: 500000 }
    events { metadata_id: 19 offset_ps: 7700000 duration_ps: 500000 } }
  event_metadata { key: 10 value { id: 10 name: "jit_step(7)" } }
  event_metadata { key: 20 value { id: 20 name: "jit_admit_chunk(8)" } }
  event_metadata { key: 11 value { id: 11
    name: "%fusion.1 = bf16[64,2048,4,128]{3,2,1,0} fusion(%p.1)" } }
  event_metadata { key: 12 value { id: 12 name: "fusion.2" } }
  event_metadata { key: 13 value { id: 13 name: "copy.3" } }
  event_metadata { key: 17 value { id: 17 name: "fusion.7" } }
  event_metadata { key: 19 value { id: 19 name: "fusion.9" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 600000 duration_ps: 5600000 }
    events { metadata_id: 3 offset_ps: 650000 duration_ps: 5500000 }
    events { metadata_id: 4 offset_ps: 900000 duration_ps: 5200000 }
    events { metadata_id: 5 offset_ps: 950000 duration_ps: 5100000 }
    events { metadata_id: 6 offset_ps: 4400000 duration_ps: 1600000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.tick" } }
  event_metadata { key: 3 value { id: 3 name: "serve.tick" } }
  event_metadata { key: 4 value { id: 4 name: "bench.step" } }
  event_metadata { key: 5 value { id: 5 name: "serve.step" } }
  event_metadata { key: 6 value { id: 6 name: "serve.step.readback#rid=3#" } } }
"""
MAPS = {"step": [{"fusion.1": "kv_gather", "fusion.2": "kv_carry"}],
        "admit_chunk": [{"fusion.7": "kv_write"},
                        {"fusion.7": "attention", "fusion.9": "sample"}]}


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(SYNTHETIC)


@pytest.fixture(scope="module")
def trace(profile):
    return trace_reduce.Trace(profile)


def test_span_names_lose_their_keywords():
    assert scopes.span_name("serve.admit#rid=3,chunks=4#") == "serve.admit"
    assert scopes.span_name("serve.step") == "serve.step"
    assert scopes.instruction("fusion.1 bf16[64,2048,4,128]") == "fusion.1"


def test_scope_time_inside_a_module_s_runs(trace):
    by, runs = scopes.scope_ns(trace.chips[0], "step", MAPS["step"])
    assert runs == 1
    assert by == {"kv_gather": 2000.0, "kv_carry": 1000.0,
                  "unscoped": 500.0}


def test_each_run_is_read_with_its_own_executable_s_map(trace):
    """Both chunk executables are `admit_chunk`: the run that holds only
    fusion.7 is the middle chunk's (the smaller map knowing all its ops),
    the other the final chunk's."""
    by, runs = scopes.scope_ns(trace.chips[0], "admit_chunk",
                               MAPS["admit_chunk"])
    assert runs == 2
    assert by == {"kv_write": 1000.0, "attention": 500.0, "sample": 500.0}


def test_gaps_are_named_by_the_program_s_spans(profile):
    out = scopes.breakdown(profile, MAPS)
    # the gap [4500, 7000] opens inside bench.step, in the program's
    # readback; gaps are named at their middle
    assert out["idle_gaps"][0] == ["serve.step.readback",
                                   pytest.approx(2500e-9)]
    assert {n for n, _ in out["idle_gaps"]} == {
        "serve.step.readback", "serve.tick", "no span"}
    assert out["idle_s_by_span"]["serve.tick"] == pytest.approx(500e-9)
    # idle inside the program's tick [650, 6150]: [650, 1000], [4500, 6150]
    assert out["idle_ms_per_serve_tick"] == pytest.approx(2000e-6)
    assert out["device_ops"][:2] == [
        ["fusion.1 bf16[64,2048,4,128] kv_gather", pytest.approx(2e-6)],
        ["fusion.7 kv_write", pytest.approx(1.5e-6)]]
    step = out["modules"]["step"]
    assert step["sync_ops_ms_per_run"] == pytest.approx(3.5e-3)
    assert step["scopes_ms_per_run"]["kv_gather"] == pytest.approx(2e-3)


@pytest.mark.parametrize("metric,ms", [("kv_gather_ms.decode", 2e-3),
                                       ("kv_carry_ms.decode", 1e-3),
                                       ("attention_ms.decode", 0.0),
                                       ("mlp_ms.decode", 0.0)])
def test_readers_on_the_synthetic_trace(trace, metric, ms):
    run = harness.Run(cell=None, seconds=1.0, setup_s=0.0, token_times={},
                      arrivals={}, chips=1, peaks={}, trace=trace)
    run.op_scopes = MAPS
    assert harness.reader(metric)(run) == pytest.approx(ms)


def test_readers_read_nothing_without_scopes(trace):
    """A run that cannot name its ops (no cell to rebuild, a program
    without op_scopes, executables without scopes) and a run without a
    trace read nothing."""
    run = harness.Run(cell=None, seconds=1.0, setup_s=0.0, token_times={},
                      arrivals={}, chips=1, peaks={}, trace=trace)
    assert harness.reader("kv_carry_ms.decode")(run) is None
    run.op_scopes = {"step": [{}]}      # an executable without scopes
    assert harness.reader("kv_carry_ms.decode")(run) is None
    run.trace = None
    run.op_scopes = MAPS
    assert harness.reader("kv_carry_ms.decode")(run) is None


def test_a_failed_rebuild_reads_nothing_and_does_not_raise(monkeypatch,
                                                           capsys):
    run = harness.Run(cell=tiny_cell("backlog"), seconds=1.0, setup_s=0.0,
                      token_times={}, arrivals={}, chips=1, peaks={})

    def fail(*a, **k):
        raise RuntimeError("no room for a second replica")

    monkeypatch.setattr(harness, "build", fail)
    assert scopes.op_scopes(run) is None
    assert scopes.op_scopes(run) is None          # tried once
    assert "no room for a second replica" in capsys.readouterr().err


def test_scope_maps_from_a_replica_built_anew():
    """Without a map on the run, the readers build the cell's replica and
    read its executables' scopes (the tiny configuration on the CPU)."""
    run = harness.Run(cell=tiny_cell("backlog"), seconds=1.0, setup_s=0.0,
                      token_times={}, arrivals={}, chips=1, peaks={})
    got = scopes.op_scopes(run)
    assert set(got) == {"step", "admit_chunk"} and run.op_scopes is got
    assert "kv_carry" in got["step"][0].values()
    assert scopes.op_scopes(run) is got
