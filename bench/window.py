"""The measured window: an open-loop generator driving ``Scheduler.submit``
and ``Scheduler.step``, every token stamped by the host clock through the
request's ``on_token`` hook, and what the metric readers need from it.

Each request is timed from its due time, not from when the generator got
round to submitting it, so a stall in the server shows in every request
due during it; how late the generator ran is kept apart.

Every collection Python's collector makes in the window is kept with
its generation and length, with the heap's size at the window's start
and the longest steps, and printed: a full collection stops the host for
a time that grows with the heap, so a stall of the host can be tied to
one or ruled out.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench import trace


def _annotate(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Window:
    """What one window served, and the counters around it."""
    t0: float
    t_end: float
    setup_s: float
    due: dict                    # uid -> absolute due time
    prompt_len: dict             # uid -> prompt tokens
    stamps: dict                 # uid -> [host time of each token]
    submitted: int
    lateness: list               # submit time - due time, per request
    steps: int                   # scheduler steps run in the window
    stats: dict                  # Scheduler.stats() counters of the window
    chunk_size: int
    num_slots: int
    dims: object                 # the reference module's dims(cfg, params)
    peaks: dict
    trace: dict | None = None    # normalized device trace (--trace 1)
    host: dict = dataclasses.field(default_factory=dict)  # heap, GC, steps

    @property
    def window_s(self) -> float:
        return self.t_end - self.t0

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t_end

    def tokens(self) -> int:
        return sum(1 for ts in self.stamps.values() for t in ts
                   if self.in_window(t))

    def gaps_s(self) -> list[float]:
        """Every gap between consecutive tokens of one request that ends
        in the window, over all requests."""
        return [b - a for ts in self.stamps.values()
                for a, b in zip(ts, ts[1:]) if self.in_window(b)]

    def ttfts_s(self) -> list[float]:
        """Due time to first token, for every request whose first token
        fell in the window."""
        return [ts[0] - self.due[u] for u, ts in self.stamps.items()
                if ts and self.in_window(ts[0])]

    def first_tokens(self) -> int:
        return len(self.ttfts_s())

    def decode_tokens(self) -> int:
        return self.tokens() - self.first_tokens()

    def prefill_rows(self) -> int:
        """Prompt tokens of the requests whose prefill completed in the
        window (their first token fell in it)."""
        return sum(self.prompt_len[u] for u, ts in self.stamps.items()
                   if ts and self.in_window(ts[0]))

    def served_flops(self) -> int:
        """Model operations of the work the window served: each completed
        prompt (attention over its own prefix), each decoded token at its
        context, and the LM head once per emitted token."""
        d = self.dims
        fl = 0
        for u, ts in self.stamps.items():
            P = self.prompt_len[u]
            for i, t in enumerate(ts):
                if not self.in_window(t):
                    continue
                if i == 0:
                    fl += d.prompt_flops(P)
                else:
                    fl += d.token_flops(P + i)
                fl += d.head_flops()
        return fl


def fill(sched, built, stamps, max_steps: int = 10_000) -> int:
    """Set-up of a backlog: submit every request and step until each slot
    that holds a request is decoding (has its first token), so the window
    starts from the steady state of a full server.  Returns how many
    requests it submitted."""
    for r in built:
        sched.submit(r)
    for _ in range(max_steps):
        sched.step()
        started = sum(1 for ts in stamps.values() if ts)
        if started - len(sched.finished) >= sched.num_active:
            return len(built)
    raise RuntimeError(f"the server did not fill in {max_steps} steps")


class _Collections:
    """Start time and seconds of every collection of Python's collector
    while attached."""

    def __init__(self):
        self.runs: list[tuple[int, float, float]] = []   # (gen, start, s)
        self._t = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.runs.append((info["generation"], self._t,
                              time.perf_counter() - self._t))


def run(sched, reqs, seconds: float, *, request_cls, setup_t0: float,
        dims, peaks: dict, trace_dir: str | None = None,
        counters=None) -> Window:
    """Offer ``reqs`` (``bench.traffic.Req``) for ``seconds`` and return
    the window.  A backlog (every request due at the window's start) is
    handed to the server in set-up (``fill``).  ``counters`` is called
    before and after, so a caller can take deltas of its own counters
    over exactly the window."""
    stamps: dict = {r.uid: [] for r in reqs}
    prompt_len = {r.uid: len(r.prompt) for r in reqs}

    def on_token(uid, index, tok, lp):
        stamps[uid].append(time.perf_counter())

    built = [request_cls(uid=r.uid, inputs={"tokens": r.prompt[None, :]},
                         max_new_tokens=r.max_new, on_token=on_token)
             for r in reqs]
    backlog = bool(reqs) and all(r.due_s == 0.0 for r in reqs)
    done = fill(sched, built, stamps) if backlog else 0
    sched.reset_stats()
    if counters:
        counters()
    host = {"objects": len(gc.get_objects())}
    collections = _Collections()
    gc.callbacks.append(collections)
    step_s: list[tuple[float, float]] = []      # (start, seconds)
    if trace_dir:
        trace.start(trace_dir)
    win_span = _annotate(trace.WINDOW_SPAN)
    win_span.__enter__()
    t0 = time.perf_counter()
    setup_s = t0 - setup_t0
    due = {r.uid: t0 + r.due_s for r in reqs}
    lateness = []
    i, steps = done, 0
    n = len(reqs)
    end = t0 + seconds
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        if i < n and due[reqs[i].uid] <= now:
            with _annotate("bench.submit"):
                while i < n and due[reqs[i].uid] <= now:
                    sched.submit(built[i], submit_time=due[reqs[i].uid])
                    lateness.append(time.perf_counter() - due[reqs[i].uid])
                    i += 1
        if sched.idle:
            nxt = due[reqs[i].uid] if i < n else end
            with _annotate("bench.wait"):
                time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
            continue
        t = time.perf_counter()
        with _annotate("bench.step"):
            sched.step()
        step_s.append((t, time.perf_counter() - t))
        steps += 1
    t_end = time.perf_counter()
    win_span.__exit__(None, None, None)
    gc.callbacks.remove(collections)
    host["collections"] = [(g, a - t0, s) for g, a, s in collections.runs]
    host["longest_steps"] = sorted(((s, a - t0) for a, s in step_s),
                                   reverse=True)[:3]
    if counters:
        counters()
    norm = None
    if trace_dir:
        trace.stop()
        norm = trace.normalize(trace.xplane_path(trace_dir))
    return Window(t0=t0, t_end=t_end, setup_s=setup_s, due=due,
                  prompt_len=prompt_len, stamps=stamps, submitted=i,
                  lateness=lateness, steps=steps, stats=sched.stats(),
                  chunk_size=sched.chunk_size, num_slots=sched.num_slots,
                  dims=dims, peaks=peaks, trace=norm, host=host)


def lateness_line(w: Window) -> str:
    late = np.asarray(w.lateness or [0.0]) * 1e3
    return (f"generator: {w.submitted} requests submitted, late by p50 "
            f"{np.percentile(late, 50):.3f} ms, max {late.max():.3f} ms")


def host_line(w: Window) -> str:
    h = w.host
    runs = h.get("collections", [])
    worst = max(runs, key=lambda r: r[2]) if runs else None
    steps = ", ".join(f"{s * 1e3:.1f} ms at {a:.2f} s"
                      for s, a in h.get("longest_steps", []))
    return (f"host: {h['objects']} objects at the window's start; in the "
            f"window {len(runs)} collections, longest "
            + (f"{worst[2] * 1e3:.1f} ms (generation {worst[0]}, at "
               f"{worst[1]:.2f} s)" if worst else "none")
            + f"; longest steps {steps}")
