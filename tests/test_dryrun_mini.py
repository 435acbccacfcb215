"""Mini-mesh dry-run: the full cell-building machinery (input_specs,
builders, shardings) compiles representative cells on an 8-device
(2,2,2) pod/data/model mesh — the in-suite proxy for the 512-chip sweep
recorded in EXPERIMENTS.md §Dry-run."""
import pytest


CASES = [
    ("llama3.2-1b", "train_4k", {}),
    ("llama3.2-1b", "decode_32k", {}),
    ("qwen3-moe-30b-a3b", "decode_32k", {}),
    ("rwkv6-7b", "long_500k", {}),
    ("llama3.2-1b", "decode_32k",
     {"cross": True, "strategy": "hier_rd"}),
]


@pytest.mark.dist
@pytest.mark.parametrize("arch,shape,opt", CASES,
                         ids=[f"{a}-{s}{'-x' if o else ''}"
                              for a, s, o in CASES])
def test_mini_dryrun_cell(dist_runner, arch, shape, opt):
    script = f"""
import jax
from repro.core.compat import make_mesh
from repro.launch.input_specs import build_cell
from repro.launch.hlo_analysis import summarize_compiled
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cell = build_cell({arch!r}, {shape!r}, mesh,
                  ar_strategy={opt.get("strategy", "flat")!r},
                  cross_pod_tp={opt.get("cross", False)!r})
lowered = cell.lower()
compiled = lowered.compile()
s = summarize_compiled(compiled, mesh, lowered=lowered)
assert s["flops"] > 0
print("MINI-DRYRUN-OK", s["dcn_bytes"], s["ici_bytes"])
"""
    import os, subprocess, sys
    from tests.conftest import SRC
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MINI-DRYRUN-OK" in proc.stdout
