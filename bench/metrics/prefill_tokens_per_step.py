"""Prompt tokens the prefill budget carried per scheduler step: chunk
lanes run x chunk size over the steps of the window, from the
scheduler's counters."""
SOURCE = "program_counter"
UNIT = "tokens"
LAYER = "scheduler (serving/scheduler.py)"
MOVES = "ttft_p95_ms"


def read(w):
    if not w.steps or "prefill_chunks" not in w.stats:
        return None
    return w.stats["prefill_chunks"] * w.chunk_size / w.steps
