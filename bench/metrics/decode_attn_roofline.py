"""The paged decode attention kernel's share of its roofline: the least
time the chip needs to read the KV the window's decode rows attend over,
once, over the kernel's device time in the trace.  The positions come from
the scheduler's ``decode_kv_tokens`` counter (the sum of ``pos + 1`` over
the active rows of every decode pass); each position holds a K and a V
row of ``kv_heads × head_dim`` bf16 values in every layer.  The read
bounds the kernel: its operations are 4 per position and query head
dimension, far under the bf16 peak's share of that time."""
from bench import trace
from bench.metrics.decode_attn_ms import KERNELS

SOURCE = "device_trace"
UNIT = "%"
LAYER = "attention (models/attention.py)"
MOVES = "itl_p50_ms"
KV_BYTES = 2                    # bf16 arenas


def read(w):
    tokens = w.stats.get("decode_kv_tokens")
    if w.trace is None or not tokens:
        return None
    k = trace.kernel_ns(w.trace, KERNELS) / 1e9
    if not k:
        return None
    d = w.dims
    byts = tokens * d.layers * 2 * d.kv_heads * d.head_dim * KV_BYTES
    return 100.0 * byts / w.peaks["hbm_bytes_per_s"] / k
