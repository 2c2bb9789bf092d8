"""The readers of the scheduler's host phase and first-token counters
(``Scheduler.stats()``): their arithmetic, their silence on a program
that keeps no such counters, and a traced tiny run on the CPU that
reports them."""
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**33 + 7
COUNTERS = [("host_ms", "ms"), ("queue_wait_ms", "ms"), ("prefill_ms", "ms")]


def test_phase_counter_readers():
    from bench.metrics import host_ms, prefill_ms, queue_wait_ms
    stats = {"host_s": {"step": 2.0, "sync": 1.5, "admit": 0.1},
             "host_steps": 10, "first_tokens": 4, "ttft_queue_s": 6.0,
             "ttft_prefill_s": 2.0}
    w = type("W", (), {"stats": stats})
    assert host_ms.read(w) == pytest.approx(50.0)
    assert queue_wait_ms.read(w) == pytest.approx(1500.0)
    assert prefill_ms.read(w) == pytest.approx(500.0)


@pytest.mark.parametrize("stats", [
    {"steps_run": 3},                                  # no such counters
    {"host_s": {}, "host_steps": 0, "first_tokens": 0,
     "ttft_queue_s": 0.0, "ttft_prefill_s": 0.0}])     # nothing counted
def test_counter_readers_read_nothing_without_counts(stats):
    from bench.metrics import host_ms, prefill_ms, queue_wait_ms
    w = type("W", (), {"stats": stats})
    assert host_ms.read(w) is None
    assert queue_wait_ms.read(w) is None
    assert prefill_ms.read(w) is None


def test_traced_tiny_run_reports_the_counters():
    """The tiny model served for a second under a profiler trace on the
    CPU: the window's counters read back as per-layer metrics."""
    from bench import run
    from bench.tests.test_bench_run import TRAFFIC
    with open(os.path.join(HERE, "tiny.json")) as f:
        cfg = json.load(f)
    cell = run.Cell("tiny", cfg, TRAFFIC, 1, [("setup_s", "s")], COUNTERS)
    out = run.run_cell(cell, SEED, 1.0, True)
    assert out["correct"] is True, out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == {n for n, _ in COUNTERS}
    assert m["host_ms"] > 0 and m["prefill_ms"] > 0
    assert m["queue_wait_ms"] >= 0
    assert json.dumps(out["breakdown"])
