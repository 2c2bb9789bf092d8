"""The paged decode attention kernel's share of its roofline: the least
time the chip needs to read the KV the window's decode rows attend over,
once, over the kernel's device time in the trace.  The positions come from
the scheduler's ``decode_kv_tokens`` counter (the sum of ``pos + 1`` over
the active rows of every decode pass); the bytes each position holds over
every layer come from the window's dims (``kv_bytes_per_token``).  The
read bounds the kernel: its operations are 4 per position and query head
dimension, far under the bf16 peak's share of that time."""
from bench import trace
from bench.metrics.decode_attn_ms import KERNELS

SOURCE = "device_trace"
UNIT = "%"
LAYER = "attention (models/attention.py)"
MOVES = "itl_p50_ms"


def read(w):
    tokens = w.stats.get("decode_kv_tokens")
    if w.trace is None or not tokens:
        return None
    k = trace.kernel_ns(w.trace, KERNELS) / 1e9
    if not k:
        return None
    byts = tokens * w.dims.kv_bytes_per_token()
    return 100.0 * byts / w.peaks["hbm_bytes_per_s"] / k
