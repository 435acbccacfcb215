import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST be the first two lines, before ANY other import: jax locks the
# device count on first initialization, and the multi-pod dry-run needs 512
# placeholder host devices to build the production mesh.  (Do NOT set this
# globally — smoke tests and benches must see 1 device.)
"""Multi-pod dry-run, a CPU-only tool: ``.lower().compile()`` every
(arch x shape x mesh) cell on the production meshes, built from 512 forced
host CPU devices, and record memory/cost/collective evidence.  It never
runs on a TPU; AOT compiles for a real chip are in
``tests/test_tpu_compile.py``.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
        --shape decode_32k --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out experiments/dryrun

A cell passes when the lowered module compiles on the 16x16 single-pod mesh
AND the 2x16x16 multi-pod mesh; failures (sharding mismatch, OOM at compile,
unsupported collective) are bugs in the system.
"""
import argparse
import json
import sys
import time
import traceback

import jax


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             strategy: str = "flat", cross_pod_tp: bool = False,
             out_dir=None, verbose: bool = True):
    from ..configs import shape_applicable
    from ..launch.mesh import make_production_mesh
    from ..launch.input_specs import build_cell
    from ..launch.hlo_analysis import summarize_compiled

    ok, why = shape_applicable(arch, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "strategy": strategy, "cross_pod_tp": cross_pod_tp,
           "n_devices": int(mesh.devices.size)}
    try:
        cell = build_cell(arch, shape_name, mesh, ar_strategy=strategy,
                          cross_pod_tp=cross_pod_tp)
        lowered = cell.lower()
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)
        summary = summarize_compiled(compiled, mesh, lowered=lowered)
        rec.update(summary)
        rec["status"] = "ok"
        rec["fits_16GB"] = summary["peak_bytes_per_device"] < 16e9
        if verbose:
            print(compiled.memory_analysis())
            ca = compiled.cost_analysis()
            print({k: ca[k] for k in ("flops", "bytes accessed")
                   if k in ca})
    except Exception as e:  # noqa: BLE001 — record and keep sweeping
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
        if verbose:
            traceback.print_exc()
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{mesh_kind}__{arch}__{shape_name}__{strategy}"
        if cross_pod_tp:
            tag += "__xpod"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main(argv=None):
    from ..configs import ARCH_IDS, SHAPES, all_cells

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=list(ARCH_IDS), default=None)
    p.add_argument("--shape", choices=list(SHAPES), default=None)
    p.add_argument("--mesh", choices=["single", "multi", "both"],
                   default="both")
    p.add_argument("--strategy", default="flat",
                   choices=["flat", "hier_ring", "hier_rd",
                            "hier_rd_halving"])
    p.add_argument("--cross-pod-tp", action="store_true",
                   help="TP spans the pod axis (the paper's headline "
                        "multi-node TP scenario)")
    p.add_argument("--all", action="store_true",
                   help="sweep the full 40-cell grid")
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--quiet", action="store_true")
    args = p.parse_args(argv)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for arch, shape, ok, _ in all_cells():
            cells.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            p.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    n_ok = n_skip = n_err = 0
    for arch, shape in cells:
        for mk in meshes:
            rec = run_cell(arch, shape, mk, strategy=args.strategy,
                           cross_pod_tp=args.cross_pod_tp,
                           out_dir=args.out, verbose=not args.quiet)
            s = rec["status"]
            n_ok += s == "ok"
            n_skip += s == "skipped"
            n_err += s == "error"
            mark = {"ok": "PASS", "skipped": "SKIP", "error": "FAIL"}[s]
            extra = ""
            if s == "ok":
                extra = (f" peak={rec['peak_bytes_per_device']/1e9:.2f}GB"
                         f" fits={rec['fits_16GB']}"
                         f" flops={rec['flops']:.3e}"
                         f" dcn={rec['dcn_bytes']/1e6:.2f}MB"
                         f" ici={rec['ici_bytes']/1e6:.2f}MB"
                         f" ({rec['lower_s']}s/{rec['compile_s']}s)")
            elif s == "error":
                extra = " " + rec["error"][:160]
            print(f"[{mark}] {mk:6s} {arch:22s} {shape:12s}{extra}",
                  flush=True)
    print(f"\ndry-run summary: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
