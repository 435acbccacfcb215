"""95th percentile of every gap between consecutive output tokens of a
request inside the window, in milliseconds (after the first two tokens,
which the admitting tick delivers together)."""
import numpy as np


def read(run):
    g = run.gaps_in_window()
    return float(np.percentile(g, 95)) * 1e3 if g.size else None
