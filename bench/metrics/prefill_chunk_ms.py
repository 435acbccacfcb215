"""Device time of one admission chunk, in milliseconds: the time of the
chunked-admission executables (middle and final chunks) on the first chip
over their runs."""
MODULE = "admit_chunk"   # parallel/steps.py build_admit_chunk_step


def read(run):
    if run.trace is None:
        return None
    ns, runs = run.trace.module_time(0).get(MODULE, (0.0, 0))
    return ns / runs * 1e-6 if runs else None
