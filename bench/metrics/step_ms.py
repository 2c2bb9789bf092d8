"""Device time of one serving step: the summed device time of the step
programs' executions (jit_mixed_step, jit_decode_step) in the traced
window, over their number."""
from bench import trace

SOURCE = "device_trace"
UNIT = "ms"
LAYER = "model step (models/model.py mixed_step, decode_step)"
MOVES = "itl_p50_ms"
STEP_PROGRAMS = ("mixed_step", "decode_step")


def read(w):
    if w.trace is None:
        return None
    n, ns = trace.module_runs(w.trace, STEP_PROGRAMS)
    return ns / n / 1e6 if n else None
