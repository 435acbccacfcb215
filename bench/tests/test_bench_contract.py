"""BENCHMARK.json holds what the harness needs and keeps the rules it is
checked by: names, units, sources, bounds, and a file for every config,
mix and metric reader."""
import re

import pytest

from bench_helpers import BENCH, ROOT, harness

SPEC = harness.load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_sources():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [x["name"] for x in metrics + SPEC["configs"] + SPEC["workloads"]]
    assert all(NAME.match(n) for n in names), names
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}


def test_files_exist():
    for c in SPEC["configs"]:
        cfg = harness.load_json(ROOT / c["file"])
        assert c["file"].startswith("bench/")
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert c["name"] in {w["config"] for w in SPEC["workloads"]}
    for w in SPEC["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    assert (BENCH / "peaks.json").is_file()


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_enough(cell):
    c = harness.find_cell(cell)
    e2e = {m["name"] for m in harness.metrics_of(c, False)}
    per_layer = harness.metrics_of(c, True)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e, (cell, m["name"])
    layers = {m["name"]: m["layer"] for m in SPEC["per_layer"]}
    assert all(len(v) <= 200 and "\n" not in v for v in layers.values())
