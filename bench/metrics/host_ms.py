"""Host time per scheduler step not spent waiting for the chip: the
``sched.step`` phase less the ``sched.sync`` phase (the wait for the
step's tokens), summed by the scheduler's phase counters
(``Scheduler.stats()["host_s"]``), over its steps."""
SOURCE = "program_counter"
UNIT = "ms"
LAYER = "scheduler (serving/scheduler.py)"
MOVES = "itl_p50_ms"


def read(w):
    host, steps = w.stats.get("host_s"), w.stats.get("host_steps")
    if not host or not steps:
        return None
    return (host["step"] - host.get("sync", 0.0)) / steps * 1e3
