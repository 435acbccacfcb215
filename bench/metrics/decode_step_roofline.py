"""Share of the roofline reached by the decode-step executable, in
percent: the least time a step could take on this chip, the larger of
its FLOPs over peak FLOP/s and its bytes over peak HBM bytes/s (from the
shapes and the contexts of the active slots, `flops.decode_step`), over
the step's device time.  Per step, averaged over the traced steps."""
import flops

MODULE = "step"


def read(run):
    if run.trace is None or not run.record or not run.record.steps:
        return None
    ns, runs = run.trace.module_time(0).get(MODULE, (0.0, 0))
    if not runs:
        return None
    m, tp = run.cell.config, run.cell.serve["tp"]
    pf, pb = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    bound = 0.0
    for ctx in run.record.steps:
        f, b = flops.decode_step(m, tp, [int(c) for c in ctx])
        bound += max(f / pf, b / pb)
    return 100.0 * (bound / len(run.record.steps)) / (ns * 1e-9 / runs)
