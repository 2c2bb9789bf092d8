"""The TT kernels' share of their roofline: the least time the chip needs
for the FFN's TT work the window served, max(operations / bf16 peak,
bytes / HBM bandwidth), over the kernels' device time in the trace.
Operations and bytes come from the window's dims (``tt_work``, over the
plan's modes and ranks) and the token rows served (not the padded rows
the kernels run): each call moves its rows in and out once and its cores
once.  On one v5e the bytes bound it in every cell (PERF.md, section
5)."""
from bench import trace
from bench.metrics.tt_ms import KERNELS

SOURCE = "device_trace"
UNIT = "%"
LAYER = "TT kernels (kernels/tt_contract.py)"
MOVES = "itl_p50_ms"


def read(w):
    if w.trace is None:
        return None
    k = trace.kernel_ns(w.trace, KERNELS) / 1e9
    rows = w.prefill_rows() + w.decode_tokens()
    if not k or not rows:
        return None
    calls = w.stats["steps_run"] + w.stats.get("prefill_chunks", 0)
    flops, byts = w.dims.tt_work(rows, calls)
    least = max(flops / w.peaks["bf16_flops_per_s"],
                byts / w.peaks["hbm_bytes_per_s"])
    return 100.0 * least / k
