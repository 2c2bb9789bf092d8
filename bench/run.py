#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this process finds.

    python3 bench/run.py --workload CELL --seed N --seconds S --trace 0|1

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); each metric has its reader in
``bench/metrics/<name>.py``.  The run builds the model through the
program's own entry point, draws its weights on the device from the seed,
warms up the shapes the cell's traffic uses (set-up), offers the traffic
to ``Scheduler.submit`` / ``Scheduler.step`` for ``--seconds`` (the
window), then compares a sample of what it served with the plain float32
reference.  The configuration's reference module holds its layer
equations: ``bench/reference_<name>.py`` where the file gives
``"reference": "<name>"``, else ``bench/reference.py``, whose docstring
states what the module gives; the published keys checked, the weight
laws, the counts the readers use and the reference itself all come from
it.

Standard output ends with one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics and ``breakdown``), ``device`` and,
last, ``checks``: each number compared with its limit.  The same checks
are the last lines of standard error.  Without a TPU, with fewer chips
than the cell asks for, or on a device kind missing from
``bench/peaks.py``, it exits non-zero and prints no result.

``--control 1`` (not part of a measured run) also puts the int8 control
in the program's place over the same sample, sends its numbers through
the same checks, and reports them under ``control`` with the ``correct``
they give, which has to come out false.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE = os.path.join(ROOT, ".bench_cache")
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class Refused(Exception):
    """The run cannot measure here: no result is printed."""


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list              # [(name, unit)]
    per_layer: list               # [(name, unit)]
    reference: types.ModuleType | None = None   # load_reference(config)

    def __post_init__(self):
        # the one place a run's reference module is resolved
        if self.reference is None:
            self.reference = load_reference(self.config)


def _json(path):
    with open(path) as f:
        return json.load(f)


def resolve_cell(name: str) -> Cell:
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = _json(os.path.join(ROOT, cfg["file"]))
    traffic = _json(os.path.join(BENCH, "traffic", f"{wl['traffic']}.json"))
    e2e = [(m["name"], m["unit"]) for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {n for n, _ in e2e}
    layer = [(m["name"], m["unit"]) for m in bench["per_layer"]
             if name in m.get("workloads", [name])
             and m["moves"] in reported]
    return Cell(name, config, traffic, int(wl["chips"]), e2e, layer)


def reader(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(config: dict, where: str = BENCH) -> types.ModuleType:
    """The module of the configuration's layer equations, found in
    ``where`` by the name the file gives it."""
    name = config.get("reference")
    if name is not None and not re.fullmatch(r"\w+", str(name)):
        raise Refused(f"reference {name!r} is not a module name")
    stem = "reference" if name is None else f"reference_{name}"
    path = os.path.join(where, f"{stem}.py")
    if not os.path.isfile(path):
        raise Refused(f"the configuration names reference {name!r}, and "
                      f"there is no {os.path.relpath(path, ROOT)}")
    mod = sys.modules.get(f"bench.{stem}")
    if mod is not None and os.path.samefile(mod.__file__, path):
        return mod
    spec = importlib.util.spec_from_file_location(f"bench.{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def device_check(chips: int):
    """The first device, its description and peaks; refuses anything but
    a TPU of a known kind with enough chips."""
    import jax
    from bench.peaks import peaks_for
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise Refused(f"JAX found {dev.platform!r}, not a TPU; this "
                      f"benchmark measures the chip only")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devs)}")
    try:
        peaks = peaks_for(dev.device_kind)
    except KeyError as e:
        raise Refused(str(e)) from None
    return dev, {"platform": dev.platform, "kind": dev.device_kind,
                 "count": chips}, peaks


def build_model(config: dict):
    """The model as ``launch/serve.main`` builds it."""
    import jax.numpy as jnp
    from repro.configs import build, get_config
    from repro.configs.base import TTConfig
    tt = config["tt"]
    tcfg = TTConfig(enabled=True, families=tuple(tt["families"]),
                    rank=int(tt["rank"]), backend=tt["backend"],
                    min_factor=int(tt["min_factor"]))
    dtype = jnp.dtype(config["serving"]["param_dtype"])
    mcfg = dataclasses.replace(
        get_config(config["arch"], config["variant"], tt=tcfg),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]))
    return build(mcfg, param_dtype=dtype)


def check_published(config: dict, model, ref: types.ModuleType):
    """Refuses a configuration file that lacks a key ``ref.published``
    names, or whose value departs from what the program runs."""
    ran = ref.published(model.cfg)
    missing = sorted(k for k in ran if k not in config)
    if missing:
        raise Refused(f"the configuration file lacks the published keys "
                      f"{missing} that its reference module checks")
    bad = {k: (v, config[k]) for k, v in ran.items() if v != config[k]}
    if bad:
        raise Refused(f"the program's {config['arch']} departs from the "
                      f"configuration file: {bad}")


def compile_counter():
    """A running count of traces and compilations in this process."""
    import jax
    from jax._src import dispatch
    box = [0]
    watched = {dispatch.JAXPR_TRACE_EVENT, dispatch.BACKEND_COMPILE_EVENT}

    def listen(event, duration, **kw):
        if event in watched:
            box[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    return box


def warm_up(sched, config, request_cls, vocab: int) -> None:
    """Compile, from the persistent cache where it holds them, exactly
    the programs the window drives: the mixed step at (lanes, chunk), the
    masked decode step, and the pick at one row and at every slot."""
    import numpy as np
    C = int(config["serving"]["chunk_size"])
    toks = (np.arange(C + 1, dtype=np.int32) * 7919) % vocab
    sched.submit(request_cls(uid=-1, inputs={"tokens": toks[None]},
                             max_new_tokens=3))
    sched.run()
    sched.reset_stats()


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             control: bool = False, chip=None) -> dict:
    """One run of ``cell``; ``chip`` is ``device_check``'s answer, or None
    to run on whatever device JAX has (the CPU tests)."""
    import jax
    from bench import check, traffic, weights, window
    from repro.kernels import plan as ttplan
    from repro.serving.scheduler import Request, Scheduler

    if chip is not None:
        dev, device, peaks = chip
    else:
        from bench.peaks import PEAKS
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": 1}
        peaks = next(iter(PEAKS.values()))
    cfg, srv, ref = cell.config, cell.config["serving"], cell.reference
    model = build_model(cfg)
    check_published(cfg, model, ref)
    params = weights.make_params(model.abstract_params(), seed,
                                 getattr(ref, "LAWS", None))
    dims = ref.dims(cfg, params)
    sched = Scheduler(model, params, num_slots=int(srv["num_slots"]),
                      cache_len=int(srv["cache_len"]), eos_id=None,
                      paged=True, block_size=int(srv["block_size"]),
                      num_blocks=int(srv["num_blocks"]), chunk_prefill=True,
                      chunk_size=int(srv["chunk_size"]),
                      prefill_budget=int(srv["prefill_budget"]))
    warm_up(sched, cfg, Request, int(cfg["vocab_size"]))
    reqs = traffic.generate(cell.traffic, seed, seconds,
                            int(cfg["vocab_size"]))
    compiles = compile_counter()
    counts = []

    def counters():
        counts.append((compiles[0], ttplan.plan_resolutions()))

    trace_dir = os.path.join(CACHE, "trace") if traced else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    w = window.run(sched, reqs, seconds, request_cls=Request,
                   setup_t0=T_PROC, dims=dims, peaks=peaks,
                   trace_dir=trace_dir, counters=counters)
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(window.lateness_line(w), file=sys.stderr)
    print(window.host_line(w), file=sys.stderr)
    stats = dev.memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    finished = list(sched.finished)
    failed = sum(1 for f in finished if f.finish_reason != "length")

    if traced:
        from bench import trace
        device["busy_s"] = trace.busy_s(w.trace)
        device["window_s"] = trace.window_s(w.trace)
    names = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for name, unit in names:
        v = reader(name).read(w)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": unit}

    print(f"window: {w.window_s:.3f} s, {w.steps} steps, {w.tokens()} "
          f"tokens, {len(finished)} requests finished; metrics "
          f"{json.dumps(metrics)}", file=sys.stderr)
    # the program's state leaves the chip before the reference runs
    sched.cache = None
    del sched
    gc.collect()
    prompts = {r.uid: r.prompt for r in reqs}
    pairs = check.sample(finished, prompts, seed)
    t_ref = time.perf_counter()
    ctrl = ref.Reference(params, cfg, "int8") if control else None
    prog, ctrl_nums, n_served = check.compare(ref.Reference(params, cfg),
                                              pairs, ctrl)
    t_ref = time.perf_counter() - t_ref
    limits = cfg["correct"]
    checks = check.checks(prog, limits)
    checks["plan_resolutions"] = {"value": counts[1][1] - counts[0][1],
                                  "limit": 0}
    checks["compiles_in_window"] = {"value": counts[1][0] - counts[0][0],
                                    "limit": 0}
    correct = failed == 0 and check.passes(checks)
    print(f"reference: {len(pairs)} requests, {n_served} served tokens "
          f"compared in {t_ref:.1f} s", file=sys.stderr)
    out = {"correct": correct, "attempted": w.submitted, "failed": failed,
           "metrics": metrics, "device": device}
    if traced:
        from bench import trace
        out["breakdown"] = trace.breakdown(w.trace)
    if ctrl_nums is not None:
        con = check.checks(ctrl_nums, limits)
        out["control"] = {"correct": check.passes(con), "checks": con}
        for k, c in con.items():
            print(f"control check {k}: {c['value']} (limit {c['limit']})",
                  file=sys.stderr)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--control", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    try:
        cell = resolve_cell(args.workload)
        chip = device_check(cell.chips)
        from repro.launch.cache import enable_compile_cache
        enable_compile_cache()
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       control=bool(args.control), chip=chip)
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
