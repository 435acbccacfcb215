import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Roofline analysis per (arch x shape x mesh) — deliverable (g).  A
CPU-only tool: it compiles on 512 forced host CPU devices (set above,
before JAX loads) and models the chip; nothing here runs on a TPU.

Terms (per chip, TPU v5e):
    compute    = HLO_FLOPs / 197 TFLOP/s
    memory     = HLO_bytes / 819 GB/s          (bf16-corrected, see below)
    collective = ICI_bytes / 45 GB/s + DCN_bytes / 6.25 GB/s

Methodology notes (full discussion in EXPERIMENTS.md):
* XLA's ``cost_analysis`` counts a ``lax.scan`` body ONCE, so the sweep
  compiles 1-layer and 2-layer UNROLLED probe variants at identical
  per-device shapes and reconstructs totals linearly:
  total = m(1) + (L-1) * (m(2) - m(1)); training cells scale by the real
  grad-accumulation microbatch count.  Probes disable attention chunking
  (chunk loops would be undercounted the same way).
* The CPU backend upcasts bf16 compute to f32; collective bytes therefore
  come from the *lowered* HLO (logical dtypes), and HLO memory bytes are
  reported raw and bf16-corrected (x0.5 — exact for the bf16-dominated
  inference streams, conservative for f32 gradient traffic).
* MODEL_FLOPS = 6 N_active D (train) / 2 N_active D (inference) per token;
  the ratio to HLO_FLOPs surfaces remat recompute, attention, dead-slot
  padding and dispatch overheads.
"""
import argparse
import dataclasses
import json
import sys
from typing import Dict, Optional

import numpy as np

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 4.5e10      # ~50 GB/s/link, one link conservatively
DCN_BW = 6.25e9      # per-chip share of pod-level DCN


def _probe_once(arch, shape_name, mesh, *, n_dec, n_enc, strategy,
                cross_pod_tp, **cell_kw):
    import dataclasses as dc
    import jax
    from ..configs import get_config, cell_plan
    from ..launch.input_specs import build_cell
    from ..launch.hlo_analysis import summarize_compiled
    cfg = get_config(arch)
    plan = cell_plan(arch, shape_name)
    over = {"n_layers": n_dec}
    if cfg.enc_layers:
        over["enc_layers"] = n_enc
    cfg2 = dc.replace(cfg, **over)
    shape = plan.shape
    probe_kw = {}
    if shape.kind == "train" and plan.microbatches > 1:
        # one microbatch at the per-microbatch batch size
        from ..configs.registry import Shape
        shape2 = Shape(shape.name, shape.seq_len,
                       shape.global_batch // plan.microbatches, shape.kind)
        probe_kw["shape_override"] = shape2
    cell = build_cell(arch, shape_name, mesh, ar_strategy=strategy,
                      cross_pod_tp=cross_pod_tp, cfg_override=cfg2,
                      scan_layers=False, probe=True, **probe_kw,
                      **cell_kw)
    lowered = cell.lower()
    compiled = lowered.compile()
    return summarize_compiled(compiled, mesh, lowered=lowered)


def _lin(m1: Dict, m2: Dict, n: int, keys) -> Dict[str, float]:
    out = {}
    for k in keys:
        a, b = float(m1[k]), float(m2[k])
        out[k] = a + (n - 1) * (b - a)
    return out


_KEYS = ("flops", "bytes_accessed", "ici_bytes", "dcn_bytes",
         "wire_ici_bytes", "wire_dcn_bytes")


def roofline_cell(arch: str, shape_name: str, mesh_kind: str, *,
                  strategy: str = "flat", cross_pod_tp: bool = False,
                  dryrun_dir: str = "experiments/dryrun",
                  variant: str = "", **cell_kw) -> Dict:
    from ..configs import get_config, cell_plan, shape_applicable
    from ..launch.mesh import make_production_mesh

    ok, why = shape_applicable(arch, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": why}
    cfg = get_config(arch)
    plan = cell_plan(arch, shape_name)
    shape = plan.shape
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = int(mesh.devices.size)

    # probes: (dec, enc) layer counts
    if cfg.enc_layers:
        m11 = _probe_once(arch, shape_name, mesh, n_dec=1, n_enc=1,
                          strategy=strategy, cross_pod_tp=cross_pod_tp,
                          **cell_kw)
        m21 = _probe_once(arch, shape_name, mesh, n_dec=2, n_enc=1,
                          strategy=strategy, cross_pod_tp=cross_pod_tp,
                          **cell_kw)
        m12 = _probe_once(arch, shape_name, mesh, n_dec=1, n_enc=2,
                          strategy=strategy, cross_pod_tp=cross_pod_tp,
                          **cell_kw)
        tot = {}
        for k in _KEYS:
            a = float(m11[k])
            bd = float(m21[k]) - a
            be = float(m12[k]) - a
            tot[k] = a + (cfg.n_layers - 1) * bd + (cfg.enc_layers - 1) * be
    else:
        m1 = _probe_once(arch, shape_name, mesh, n_dec=1, n_enc=0,
                         strategy=strategy, cross_pod_tp=cross_pod_tp,
                         **cell_kw)
        m2 = _probe_once(arch, shape_name, mesh, n_dec=2, n_enc=0,
                         strategy=strategy, cross_pod_tp=cross_pod_tp,
                         **cell_kw)
        tot = _lin(m1, m2, cfg.n_layers, _KEYS)

    if shape.kind == "train" and plan.microbatches > 1:
        for k in _KEYS:
            tot[k] *= plan.microbatches

    t_compute = tot["flops"] / PEAK_FLOPS
    bytes_bf16 = tot["bytes_accessed"] * 0.5
    t_memory = bytes_bf16 / HBM_BW
    t_coll = tot["wire_ici_bytes"] / ICI_BW + tot["wire_dcn_bytes"] / DCN_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)
    bound = sum(terms.values())
    frac = terms[dominant] / bound if bound > 0 else 0.0

    # MODEL_FLOPS (useful) per device
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens / n_dev
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens / n_dev
    else:
        model_flops = 2.0 * n_active * shape.global_batch / n_dev

    suggest = {
        "compute_s": "raise MXU utilization: fuse elementwise chains, "
                     "MXU-align tiles, drop dead-slot padding",
        "memory_s": "cut HBM traffic: int8 weights/KV-cache, larger "
                    "batch per weight read, fuse to avoid re-reads",
        "collective_s": "hierarchical RD over the slow axis, int8 "
                        "exchange, overlap AR with compute",
    }[dominant]

    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "strategy": strategy, "cross_pod_tp": cross_pod_tp,
           "variant": variant,
           "status": "ok", "n_devices": n_dev,
           "hlo_flops_per_dev": tot["flops"],
           "hlo_bytes_per_dev_raw": tot["bytes_accessed"],
           "hlo_bytes_per_dev_bf16corr": bytes_bf16,
           "ici_bytes_per_dev": tot["ici_bytes"],
           "dcn_bytes_per_dev": tot["dcn_bytes"],
           "wire_ici_bytes_per_dev": tot["wire_ici_bytes"],
           "wire_dcn_bytes_per_dev": tot["wire_dcn_bytes"],
           **terms,
           "dominant": dominant.replace("_s", ""),
           "dominant_frac": frac,
           "model_flops_per_dev": model_flops,
           "useful_flops_ratio": model_flops / max(tot["flops"], 1.0),
           "bound_step_s": bound,
           "move_dominant": suggest}
    # attach memory evidence from the scanned dry-run record if present
    tag = f"{mesh_kind}__{arch}__{shape_name}__flat.json"
    p = os.path.join(dryrun_dir, tag)
    if os.path.exists(p):
        with open(p) as f:
            d = json.load(f)
        rec["peak_bytes_per_device_xla"] = d.get("peak_bytes_per_device")
        rec["argument_bytes_per_device"] = d.get("argument_bytes_per_device")
    return rec


def main(argv=None):
    from ..configs import ARCH_IDS, SHAPES, all_cells
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--mesh", choices=["single", "multi"], default="single")
    p.add_argument("--strategy", default="flat")
    p.add_argument("--cross-pod-tp", action="store_true")
    p.add_argument("--all", action="store_true")
    p.add_argument("--out", default="experiments/roofline")
    args = p.parse_args(argv)

    cells = ([(a, s) for a, s, ok, _ in all_cells() if ok]
             if args.all else [(args.arch, args.shape)])
    os.makedirs(args.out, exist_ok=True)
    for arch, shape in cells:
        rec = roofline_cell(arch, shape, args.mesh, strategy=args.strategy,
                            cross_pod_tp=args.cross_pod_tp)
        tag = f"{args.mesh}__{arch}__{shape}__{args.strategy}"
        if args.cross_pod_tp:
            tag += "__xpod"
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
        if rec["status"] == "ok":
            print(f"{arch:22s} {shape:12s} {args.mesh:6s} "
                  f"C={rec['compute_s']*1e3:8.3f}ms "
                  f"M={rec['memory_s']*1e3:8.3f}ms "
                  f"N={rec['collective_s']*1e3:8.3f}ms "
                  f"dom={rec['dominant']:10s} "
                  f"useful={rec['useful_flops_ratio']:.2f}", flush=True)
        else:
            print(f"{arch:22s} {shape:12s} SKIP ({rec['reason'][:40]})",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
