"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are named in
BENCHMARK.json at the root of the checkout.  With --trace 0 the result
holds the cell's end-to-end metrics; with --trace 1 its per-layer metrics,
read from a profiler trace of the window.  The last line of standard
output is the result, a JSON object; the last lines of standard error
give each number the correctness check compared, beside its limit.  A run
that finds no TPU, or fewer chips than the cell asks for, exits non-zero
and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb to this path")
    ap.add_argument("--trace-seconds", type=float,
                    help="trace this much of the window (default: "
                         "harness.TRACE_SECONDS)")
    args = ap.parse_args(argv)
    import harness
    try:
        cell = harness.find_cell(args.workload)
        res = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace),
            t_start=T_START, keep_trace=args.keep_trace,
            trace_seconds=args.trace_seconds or harness.TRACE_SECONDS)
    except harness.Failed as e:
        print(f"bench: FAILED: {e}", file=sys.stderr, flush=True)
        return 3
    for line in harness.check_lines(res):
        print(line, file=sys.stderr, flush=True)
    print(harness.result_line(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
