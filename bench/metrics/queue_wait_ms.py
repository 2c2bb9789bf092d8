"""Mean time a request waited for admission before its first token: from
its submit (due) time to the admission that led to its first token, over
the first tokens of the window, from the scheduler's counters
(``ttft_queue_s`` / ``first_tokens``)."""
SOURCE = "program_counter"
UNIT = "ms"
LAYER = "scheduler (serving/scheduler.py)"
MOVES = "ttft_p95_ms"


def read(w):
    n = w.stats.get("first_tokens")
    return w.stats["ttft_queue_s"] / n * 1e3 if n else None
