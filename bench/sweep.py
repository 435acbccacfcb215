"""Find the knee of an open-loop cell: the highest arrival rate at which
the queue stops growing over a window.  Run once, on the chip, when the
cell's rate is chosen; the rate goes into the mix's file at 4/5 of it.

    python3 bench/sweep.py --workload <cell> --rates 3,4,5,6 --seconds 20

Prints, per rate, the waiting requests over each half of the window and
at its close, with the time to first token and the inter-token gap.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import harness
    cell = harness.find_cell(args.workload)
    t0 = T_START
    for rate in [float(r) for r in args.rates.split(",")]:
        c = dataclasses.replace(cell, mix=dict(cell.mix, rate_per_s=rate))
        res = harness.run_cell(c, args.seed, args.seconds, False,
                               t_start=t0, do_check=False)
        t0 = time.perf_counter()
        print(json.dumps({"rate_per_s": rate, "queue": res["queue"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
