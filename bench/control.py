"""Readings for the correctness limit of a cell, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, one run of the cell as `run.py` makes it (same window,
same load, same sample of served tokens), then the float32 reference and
its control, the same forward with every linear layer in fp8.  Prints per
seed the widest and the mean gap of the served tokens (the program) and
of the tokens the control puts first, and the harness's verdict on each:
`correct` for the program, `control_correct` for the control's tokens put
in the served tokens' place, which has to come out false.  A limit goes
between the program's largest and the control's smallest reading of the
number the configuration compares.  The benchmark's own runs never run
the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import harness
    cell = harness.find_cell(args.workload)
    rows = []
    t0 = T_START
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = harness.run_cell(cell, seed, args.seconds, False, t_start=t0,
                               control=True)
        t0 = time.perf_counter()
        r = res["readings"]
        # the control's tokens, judged by the harness's own comparison
        as_control = dict(r, logit_gap_max=r["control_gap_max"],
                          logit_gap_mean=r["control_gap_mean"])
        ctrl = harness.checks_of(cell, as_control,
                                 res["checks"]["quarantined_steps"]["value"])
        row = {"seed": seed, "correct": res["correct"],
               "control_correct": harness.verdict(ctrl),
               "program_gap": r["logit_gap_max"],
               "control_gap": r["control_gap_max"],
               "program_gap_mean": r["logit_gap_mean"],
               "control_gap_mean": r["control_gap_mean"],
               "argmax_agreement": r["argmax_agreement"],
               "served_tokens": r["served_tokens_checked"],
               "checks": res["checks"], "control_checks": ctrl,
               "metrics": {k: v["value"] for k, v in res["metrics"].items()},
               "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = {"workload": args.workload, "seeds": len(rows),
           "program_correct": sum(r["correct"] for r in rows),
           "control_correct": sum(r["control_correct"] for r in rows)}
    for tag, p, c in (("", "program_gap", "control_gap"),
                      ("mean_", "program_gap_mean", "control_gap_mean")):
        prog = max(r[p] for r in rows)
        ctrl = min(r[c] for r in rows)
        out.update({f"program_{tag}gap_max": prog,
                    f"control_{tag}gap_min": ctrl,
                    f"{tag}ratio": ctrl / prog if prog else None})
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
