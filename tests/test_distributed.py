"""Multi-device (8 simulated host devices) system tests, run in
subprocesses so the main pytest process keeps its single-device view."""
import pytest


@pytest.mark.dist
def test_collective_strategies(dist_runner):
    out = dist_runner("case_collectives.py")
    assert "collectives OK" in out


@pytest.mark.dist
def test_overlap_autotune(dist_runner):
    out = dist_runner("case_overlap_autotune.py")
    assert "overlap+autotune OK" in out


@pytest.mark.dist
def test_decode_parity(dist_runner):
    out = dist_runner("case_decode_parity.py")
    assert "decode parity OK" in out


@pytest.mark.dist
def test_serve_trace_parity(dist_runner):
    out = dist_runner("case_serve.py")
    assert "serve OK" in out


@pytest.mark.dist
def test_prefix(dist_runner):
    out = dist_runner("case_prefix.py")
    assert "prefix OK" in out


@pytest.mark.dist
def test_spec_decode_parity(dist_runner):
    out = dist_runner("case_spec.py")
    assert "spec OK" in out


@pytest.mark.dist
def test_disagg_mesh_parity(dist_runner):
    out = dist_runner("case_disagg.py")
    assert "disagg OK" in out


@pytest.mark.dist
def test_sp_prefill_parity(dist_runner):
    out = dist_runner("case_sp_prefill.py")
    assert "sp prefill OK" in out


@pytest.mark.dist
def test_train_parity(dist_runner):
    out = dist_runner("case_train_parity.py")
    assert "train parity OK" in out


@pytest.mark.dist
def test_elastic_restart(dist_runner):
    out = dist_runner("case_elastic.py")
    assert "elastic OK" in out


@pytest.mark.dist
def test_faults_injected(dist_runner):
    out = dist_runner("case_faults.py")
    assert "faults OK" in out


@pytest.mark.dist
def test_quant_allreduce(dist_runner):
    out = dist_runner("case_quant_ar.py")
    assert "quant_ar OK" in out


@pytest.mark.dist
def test_router_fleet(dist_runner):
    out = dist_runner("case_router.py")
    assert "router OK" in out


@pytest.mark.dist
def test_replica_devices(dist_runner):
    out = dist_runner("case_replica_devices.py", 4)
    assert "replica devices OK" in out


@pytest.mark.dist
def test_wire_bytes(dist_runner):
    out = dist_runner("case_wire_bytes.py")
    assert "wire bytes OK" in out


@pytest.mark.dist
def test_pp_and_perf_parity(dist_runner):
    out = dist_runner("case_pp_and_perf.py")
    assert "pp+perf case OK" in out
