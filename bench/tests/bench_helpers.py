"""Shared by the benchmark's CPU tests: a tiny cell built from the real
harness, run without the chip check, at a size a test run can hold."""
from __future__ import annotations

import copy
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

TINY = json.loads((BENCH / "tests" / "tiny.json").read_text())
MIXES = {
    "backlog": {"loop": "backlog", "waiting_per_slot": 2, "block": 16,
                "prompt_tokens": {"median": 40, "sigma": 0.6, "min": 8,
                                  "max": 100},
                "output_tokens": {"median": 24, "sigma": 0.6, "min": 8,
                                  "max": 40}},
    "open": {"loop": "open", "rate_per_s": 20.0, "arrival_cv": 2.0,
             "prompt_tokens": {"median": 60, "sigma": 0.6, "min": 16,
                               "max": 120},
             "output_tokens": {"median": 8, "sigma": 0.6, "min": 2,
                               "max": 20}},
}


CELL_OF = {"backlog": "codeqwen1.5-7b.1chip.decode-sat",
           "open": "codeqwen1.5-7b.1chip.prefill-open"}


def tiny_cell(loop: str = "backlog", chips: int = 1, **config) -> "harness.Cell":
    cfg = copy.deepcopy(TINY)
    serve = dict(cfg["serve"], **config.pop("serve", {}))
    cfg.update(config)
    cfg["serve"] = serve
    # named as the real cell of the same loop, so it reports its metrics
    name = CELL_OF[loop]
    wl = {"name": name, "config": "tiny", "traffic": loop, "chips": chips}
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    return harness.Cell(name, wl, cfg, copy.deepcopy(MIXES[loop]), bench)


def run_tiny(cell, seed: int = 2 ** 31 + 7, seconds: float = 1.0,
             **kw) -> dict:
    return harness.run_cell(cell, seed, seconds, False,
                            t_start=time.perf_counter(), require_chip=False,
                            compile_cache=False, **kw)
