"""Share of the roofline reached by the admission-chunk executables, in
percent: per chunk the larger of FLOPs over peak FLOP/s and bytes over
peak HBM bytes/s (real prompt tokens only, `flops.prefill_chunk`), over
the chunk's device time; averaged over the traced window's chunks."""
import flops

MODULE = "admit_chunk"


def read(run):
    if run.trace is None or not run.record or not run.record.prompts:
        return None
    ns, runs = run.trace.module_time(0).get(MODULE, (0.0, 0))
    if not runs:
        return None
    m, sv = run.cell.config, run.cell.serve
    pf, pb = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    bounds = [max(f / pf, b / pb)
              for n in run.record.prompts
              for f, b in (flops.prefill_chunk(m, sv["tp"], *c)
                           for c in flops.prompt_chunks(n, sv["admit_chunk"]))]
    return 100.0 * (sum(bounds) / len(bounds)) / (ns * 1e-9 / runs)
