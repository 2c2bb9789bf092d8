"""Mean time from a request's admission to its first token: its prefill,
spread over chunk lanes, over the first tokens of the window, from the
scheduler's counters (``ttft_prefill_s`` / ``first_tokens``)."""
SOURCE = "program_counter"
UNIT = "ms"
LAYER = "scheduler (serving/scheduler.py)"
MOVES = "ttft_p95_ms"


def read(w):
    n = w.stats.get("first_tokens")
    return w.stats["ttft_prefill_s"] / n * 1e3 if n else None
