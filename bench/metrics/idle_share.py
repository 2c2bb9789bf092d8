"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window."""
from bench import trace

SOURCE = "device_trace"
UNIT = "%"
LAYER = "device"
MOVES = "itl_p50_ms"


def read(w):
    if w.trace is None or not any(d["ops"] for d in
                                  w.trace["devices"].values()):
        return None
    return 100.0 * (1.0 - trace.busy_s(w.trace) / trace.window_s(w.trace))
