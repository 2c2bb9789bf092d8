"""Median of every inter-token gap that ended in the window, over all
requests (not a median of per-request means)."""
import numpy as np

SOURCE = "host_clock"
UNIT = "ms"


def read(w):
    g = w.gaps_s()
    return float(np.percentile(g, 50)) * 1e3 if g else None
