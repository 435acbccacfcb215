"""95th percentile of the time from a request's scheduled arrival to its
first token, over every request due in the window, in milliseconds.  A
request that never got one counts as infinitely late."""
import numpy as np


def read(run):
    t = run.ttfts()
    if not t.size:
        return None
    v = float(np.percentile(t, 95))
    return v * 1e3 if np.isfinite(v) else None
