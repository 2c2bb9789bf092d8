"""Device time of the Pallas TT kernels per serving step: the summed
device time of their ops in the traced window, over the step programs'
executions."""
from bench import trace
from bench.metrics.step_ms import STEP_PROGRAMS

SOURCE = "device_trace"
UNIT = "ms"
LAYER = "TT kernels (kernels/tt_contract.py)"
MOVES = "itl_p50_ms"
# how the trace names the kernels' ops: the jitted wrappers of the
# pallas_calls name the custom-call instructions
KERNELS = ("_tt_fused_chain_call", "_tt_step_call")


def read(w):
    if w.trace is None:
        return None
    k = trace.kernel_ns(w.trace, KERNELS)
    n, _ = trace.module_runs(w.trace, STEP_PROGRAMS)
    return k / n / 1e6 if k and n else None
