"""Model FLOP/s utilization of the whole served step over the traced
window, in percent: the model FLOPs of every token the window processed
(prompt tokens admitted and tokens decoded, `flops.token_flops`) over
window seconds x chips x peak bf16 FLOP/s.  Read in the cells that
report output_tok_s."""
import flops


def read(run):
    rec = run.record
    if run.trace is None or not rec or not (rec.steps or rec.prompts):
        return None
    m = run.cell.config
    total = sum(flops.token_flops(m, int(c), True)
                for ctx in rec.steps for c in ctx)
    for n in rec.prompts:
        total += sum(flops.token_flops(m, p + 1, p == n - 1)
                     for p in range(n))
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * total / (run.trace.window_s * peak)
