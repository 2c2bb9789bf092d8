"""Device trace: capture the measured window with JAX's profiler, reduce
the ``.xplane.pb`` to a small normalized form, and compute from it the
device's busy time, per-op self time, named-kernel time and the longest
idle gaps, each gap named by the benchmark's host span it fell in.

The normalized form (what the tests check the reduction on) is a dict

    {"window": [start_ns, end_ns],
     "devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
                         "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

on the profiler's one clock.  On a TPU an op's name is its HLO
instruction text; a Pallas kernel's custom-call is named after the
jitted function that wraps its ``pallas_call``.
"""
from __future__ import annotations

import glob
import os
import re

HOST_PREFIX = "bench."            # the benchmark's own host spans
WINDOW_SPAN = "bench.window"


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # host spans only, no Python calls
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def xplane_path(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def normalize(path: str) -> dict:
    """Read an ``.xplane.pb`` into the normalized form."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [[e.name, float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events]
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith(HOST_PREFIX):
                        continue
                    if e.name == WINDOW_SPAN:
                        window = [float(e.start_ns), float(e.end_ns)]
                    else:
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    host.sort(key=lambda h: h[1])
    return {"window": window, "devices": devices, "host": host}


# ------------------------------------------------------------ reductions
def _clip(start, dur, lo, hi):
    a, b = max(start, lo), min(start + dur, hi)
    return (a, b) if b > a else None


def busy_intervals(ops, window) -> list[tuple[float, float]]:
    """Union of the op intervals inside the window, merged and sorted."""
    lo, hi = window
    iv = sorted(c for o in ops if (c := _clip(o[1], o[2], lo, hi)))
    out: list[list[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(norm: dict) -> float:
    """Seconds in which an op ran, averaged over the devices traced."""
    devs = [d for d in norm["devices"].values() if d["ops"]]
    if not devs:
        return 0.0
    tot = sum(sum(b - a for a, b in busy_intervals(d["ops"], norm["window"]))
              for d in devs)
    return tot / len(devs) / 1e9


def window_s(norm: dict) -> float:
    lo, hi = norm["window"]
    return (hi - lo) / 1e9


def self_times(ops, window) -> dict[str, float]:
    """Self time (ns) by op name inside the window: an op's duration less
    the ops nested in it on the same line (a loop op contains its body)."""
    lo, hi = window
    evs = sorted((o for o in ops if _clip(o[1], o[2], lo, hi)),
                 key=lambda o: (o[1], -o[2]))
    out: dict[str, float] = {}
    stack: list[list] = []            # [name, end, self]

    def close(entry):
        out[entry[0]] = out.get(entry[0], 0.0) + entry[2]

    for name, start, dur in evs:
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        c = _clip(start, dur, lo, hi)
        span = c[1] - c[0]
        if stack:
            stack[-1][2] -= span
        stack.append([name, start + dur, span])
    while stack:
        close(stack.pop())
    return out


def kernel_ns(norm: dict, patterns) -> float:
    """Device time (ns, summed over devices) of the ops whose name
    contains any of ``patterns``."""
    lo, hi = norm["window"]
    tot = 0.0
    for d in norm["devices"].values():
        for name, start, dur in d["ops"]:
            if any(p in name for p in patterns):
                c = _clip(start, dur, lo, hi)
                if c:
                    tot += c[1] - c[0]
    return tot


def module_runs(norm: dict, patterns) -> tuple[int, float]:
    """(executions, device ns) of the compiled programs whose module name
    contains any of ``patterns``, inside the window, over all devices."""
    lo, hi = norm["window"]
    n, tot = 0, 0.0
    for d in norm["devices"].values():
        for name, start, dur in d["modules"]:
            if any(p in name for p in patterns):
                c = _clip(start, dur, lo, hi)
                if c:
                    n += 1
                    tot += c[1] - c[0]
    return n, tot


def idle_gaps(norm: dict, top: int = 10) -> list[tuple[str, float]]:
    """The longest gaps (s) between device busy intervals in the window
    on the first traced device, each named by the host span that covers
    its midpoint (``idle`` where none does)."""
    devs = [d for d in norm["devices"].values() if d["ops"]]
    if not devs:
        return []
    lo, hi = norm["window"]
    busy = busy_intervals(devs[0]["ops"], norm["window"])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        name = "idle"
        for hname, hs, hd in norm["host"]:
            if hs <= mid <= hs + hd:
                name = hname
        out.append((name, (b - a) / 1e9))
    return out


def short_name(op: str) -> str:
    """``%fusion.7 bf16[8,64] fusion`` from an op's HLO text: the
    instruction, its result type and its opcode."""
    lhs, eq, rhs = op.partition(" = ")
    if not eq:
        return op
    shape = re.match(r"[^{ ]*", rhs).group(0)
    kind = re.search(r" ([a-z][\w\-.]*)\(", rhs)
    return " ".join(x for x in (lhs, shape, kind and kind.group(1)) if x)


def breakdown(norm: dict, top: int = 10) -> dict:
    """Top device ops by self time and the longest idle gaps (seconds),
    for the result line.  Self times are summed over devices."""
    tot: dict[str, float] = {}
    for d in norm["devices"].values():
        for k, v in self_times(d["ops"], norm["window"]).items():
            tot[k] = tot.get(k, 0.0) + v
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[short_name(k), v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle_gaps(norm, top)]}
