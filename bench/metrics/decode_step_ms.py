"""Device time of one run of the decode-step executable, in
milliseconds: its module's time on the first chip over its runs."""
MODULE = "step"      # jax.jit of parallel/steps.py build_serve_step's `step`


def read(run):
    if run.trace is None:
        return None
    ns, runs = run.trace.module_time(0).get(MODULE, (0.0, 0))
    return ns / runs * 1e-6 if runs else None
