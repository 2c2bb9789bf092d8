"""95th percentile, over every request whose first token fell in the
window, of the time from its due time to that token."""
import numpy as np

SOURCE = "host_clock"
UNIT = "ms"


def read(w):
    t = w.ttfts_s()
    return float(np.percentile(t, 95)) * 1e3 if t else None
