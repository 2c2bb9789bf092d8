"""Device time of the paged decode attention kernel per serving step: the
summed device time of its ops in the traced window, over the step
programs' executions."""
from bench import trace
from bench.metrics.step_ms import STEP_PROGRAMS

SOURCE = "device_trace"
UNIT = "ms"
LAYER = "attention (models/attention.py)"
MOVES = "itl_p50_ms"
# how the trace names the kernel's ops: the jitted wrapper of its
# pallas_call names the custom-call instruction
KERNELS = ("_paged_decode_attn_call",)


def read(w):
    if w.trace is None:
        return None
    k = trace.kernel_ns(w.trace, KERNELS)
    n, _ = trace.module_runs(w.trace, STEP_PROGRAMS)
    return k / n / 1e6 if k and n else None
