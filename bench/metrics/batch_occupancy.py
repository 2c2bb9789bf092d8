"""Decode rows used per decode pass: tokens decoded in the window over
(decode passes x slots), from the scheduler's counters."""
SOURCE = "program_counter"
UNIT = "%"
LAYER = "scheduler (serving/scheduler.py)"
MOVES = "tok_s"


def read(w):
    passes = w.stats["steps_run"]
    if not passes:
        return None
    return 100.0 * w.decode_tokens() / (passes * w.num_slots)
