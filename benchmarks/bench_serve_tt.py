"""Model-level serving comparison: dense vs TT-compressed decode throughput,
fixed-batch loop vs continuous-batching scheduler (dense and block-paged
pools), swept over slot counts, plus a shared-prefix workload measuring
what hash-based prefix reuse buys at admission time.

The paper's Fig 15 compares layer-level execution; this bench closes the
loop at the model level on this host.  Per slot count B three decode loops
are measured post-compile:

  fixed — the lockstep loop (scalar cache position, jitted decode_step)
  sched — the dense slot-pool scheduler at full occupancy
  paged — the block-paged scheduler at full occupancy (same masked step,
          attention through block-table gather/scatter)

Each scheduler record carries its KV-pool bytes and (paged) the block
high-water mark — the dense-vs-paged pool-bytes column is the memory
argument of DESIGN.md §7.  The prefix workload admits N requests sharing a
long prompt prefix twice — prefix cache off vs on — and reports admission
wall time and the measured hit rate; the reduction is the prefill compute
the resident blocks saved.

The cold-start workload (DESIGN.md §13) launches ``launch.serve
--first-token`` twice as real subprocesses sharing one persistent
compilation cache: the first pays every compile (cold), the second must
re-jit NOTHING (asserted via the cache entry count) and be measurably
faster from process start to first token — the restart cost a crash-safe
deployment actually pays.

The long-prompt-adversary workload (DESIGN.md §15) queues short requests
behind one multi-thousand-token prompt and reports their p50/p95
time-to-first-token under monolithic vs chunked admission — the measured
p95 TTFT win of folding prefill chunks into the decode step.

The mesh-scaling sweep (DESIGN.md §14) serves the TT model over 1/2/4
forced host devices at a fixed slots-per-device, one subprocess per
measurement, asserting zero TT plan re-resolutions and paged≡dense token
identity on every mesh — see ``_mesh_scaling`` for how the single-core
container's forced serialization is reported vs corrected.  Results land
in ``results/BENCH_serve.json``.

Everything here runs on the CPU, parent and children alike: its timings
are CPU timings, never chip results.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.configs import build, get_config
from repro.configs.base import TTConfig
from repro.configs.shapes import concrete_batch
from repro.serving.scheduler import Request, Scheduler

from .common import header, row

RESULTS = pathlib.Path(__file__).resolve().parent.parent / "results"
BLOCK = 16


def _fixed_throughput(model, params, B, S, steps):
    """Steady-state decode tok/s of the lockstep loop (post-compile)."""
    batch = dict(concrete_batch(model.cfg, B, S), cache_len=S + steps + 2)
    logits, cache = model.jitted_prefill(S + steps + 2)(
        params, {"tokens": batch["tokens"]})
    step = model.jitted_decode_step()
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    logits, cache = step(params, cache, tok)          # compile
    jax.block_until_ready(logits)
    t0 = time.perf_counter()
    for _ in range(steps):
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        logits, cache = step(params, cache, tok)
    jax.block_until_ready(logits)
    return B * steps / (time.perf_counter() - t0)


def _sched_throughput(model, params, B, S, steps, paged):
    """Steady-state decode tok/s of a scheduler pool at full occupancy:
    B requests admitted, then ``steps`` masked decode steps with no
    admissions/retirements in the timed window.  Returns
    (tok/s, pool stats)."""
    budget = steps + 4                     # stays active through the window
    sched = Scheduler(model, params, num_slots=B,
                      cache_len=S + budget + 2, paged=paged,
                      block_size=BLOCK)
    for b in range(B):
        toks = concrete_batch(model.cfg, 1, S, seed=b)["tokens"]
        sched.submit(Request(uid=b, inputs={"tokens": toks},
                             max_new_tokens=budget))
    sched.step()                           # admissions + first masked step
    sched.step()                           # warm steady step
    t0 = time.perf_counter()
    for _ in range(steps):
        sched.step()
    return B * steps / (time.perf_counter() - t0), sched.stats()


def _prefix_workload(model, params, n_req, prefix_len, tail, steps):
    """Admission wall time of n_req requests sharing a prefix_len-token
    prompt prefix, paged pool, prefix cache off vs on.  The scheduler and
    every jit entry are warmed by the first (off) pass + a throwaway
    warm-up request per mode, so the measured difference is prefill
    compute, not compiles."""
    S = prefix_len + tail
    cache_len = S + steps + 2
    prefix = concrete_batch(model.cfg, 1, prefix_len, seed=0)["tokens"]

    def prompts(seed0):
        return [jnp.concatenate(
            [prefix, concrete_batch(model.cfg, 1, tail,
                                    seed=seed0 + i)["tokens"]], 1)
            for i in range(n_req)]

    out = {}
    for mode, use_prefix in (("off", False), ("on", True)):
        sched = Scheduler(model, params, num_slots=1, cache_len=cache_len,
                          paged=True, block_size=BLOCK,
                          prefix_cache=use_prefix)
        # warm-up: compile prefill/splice/decode (+ resume on a hit),
        # then zero the counters so only the timed pass is reported
        for uid, toks in enumerate(prompts(100)):
            sched.submit(Request(uid=-1 - uid, inputs={"tokens": toks},
                                 max_new_tokens=steps))
        sched.run()
        sched.reset_stats()
        # timed: admission wall only (submit + the admitting step), the
        # drain decode excluded — this isolates the prefill compute the
        # resident prefix blocks saved
        wall = 0.0
        for uid, toks in enumerate(prompts(200)):
            sched.submit(Request(uid=uid, inputs={"tokens": toks},
                                 max_new_tokens=steps))
            t0 = time.perf_counter()
            sched.step()
            wall += time.perf_counter() - t0
            sched.run()
        st = sched.stats()
        out[mode] = {"wall_s": wall, "hit_rate": st["prefix_hit_rate"],
                     "prefill_tokens_skipped":
                         st["prefill_tokens_skipped"]}
    out["speedup"] = out["off"]["wall_s"] / out["on"]["wall_s"]
    return out


def _cpu_child_env(repo: pathlib.Path) -> dict:
    """Environment of a child process: the repo on ``PYTHONPATH`` and JAX
    pinned to the CPU, like the parent (see :func:`run`)."""
    return dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=str(repo / "src") + os.pathsep
                + os.environ.get("PYTHONPATH", ""))


def _cold_start(arch: str = "deepseek-7b", prompt_len: int = 8,
                steps: int = 4) -> dict:
    """Process start → first token, cold vs warm, via two real serve.py
    subprocesses sharing one persistent compile cache.  Identical flags
    both runs (config differences change XLA cache keys); the warm run
    carries --assert-cache-hits so zero-recompile is enforced inside the
    measured process itself."""
    repo = pathlib.Path(__file__).resolve().parent.parent
    env = _cpu_child_env(repo)

    def launch(cache_dir: str, warm: bool) -> dict:
        cmd = [sys.executable, "-m", "repro.launch.serve", "--arch", arch,
               "--variant", "smoke", "--first-token",
               "--compile-cache", cache_dir,
               "--prompt-len", str(prompt_len), "--steps", str(steps),
               "--batch", "1", "--slots", "1"]
        if warm:
            cmd.append("--assert-cache-hits")
        out = subprocess.run(cmd, env=env, cwd=repo, capture_output=True,
                             text=True, check=True).stdout
        for line in out.splitlines():
            if line.startswith("COLD_START "):
                return json.loads(line[len("COLD_START "):])
        raise RuntimeError(f"no COLD_START line in serve output:\n{out}")

    with tempfile.TemporaryDirectory() as cache_dir:
        cold = launch(cache_dir, warm=False)
        warm = launch(cache_dir, warm=True)
    if warm["start_to_first_token_s"] >= cold["start_to_first_token_s"]:
        raise AssertionError(
            f"warm start→first-token ({warm['start_to_first_token_s']}s) "
            f"not faster than cold ({cold['start_to_first_token_s']}s) — "
            f"the persistent compile cache bought nothing")
    rec = {"arch": arch, "prompt_len": prompt_len, "steps": steps,
           "cold_start_to_first_token_s": cold["start_to_first_token_s"],
           "warm_start_to_first_token_s": warm["start_to_first_token_s"],
           "warm_speedup": round(cold["start_to_first_token_s"]
                                 / warm["start_to_first_token_s"], 2),
           "compile_cache_entries": cold["cache_entries"],
           "warm_new_compilations": (warm["cache_entries"]
                                     - cold["cache_entries"])}
    print(f"\ncold start ({arch}): start→first-token "
          f"{rec['cold_start_to_first_token_s']:.2f}s cold → "
          f"{rec['warm_start_to_first_token_s']:.2f}s warm "
          f"({rec['warm_speedup']:.2f}x, {rec['compile_cache_entries']} "
          f"cache entries, {rec['warm_new_compilations']} warm recompiles)")
    return rec


_MESH_WORKER = r'''
import json, os, re, sys, time
n = int(sys.argv[1]); k = int(sys.argv[2]); S = int(sys.argv[3])
steps = int(sys.argv[4]); windows = int(sys.argv[5])
full = bool(int(sys.argv[6]))          # census + identity on this round
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
import jax
import numpy as np
from repro.configs import build, get_config
from repro.configs.base import TTConfig
from repro.configs.shapes import concrete_batch
from repro.kernels import plan as ttplan
from repro.launch.mesh import make_serve_mesh
from repro.serving.scheduler import Request, Scheduler
import dataclasses

BLOCK = 16
base = get_config("deepseek_7b", "smoke")
cfg = dataclasses.replace(base, tt=TTConfig(
    enabled=True, families=("ffn", "attn"), rank=4, min_factor=2))
model = build(cfg)
params = model.init(jax.random.PRNGKey(0))
mesh = make_serve_mesh(n)
out = {"devices": n}


def best_window(B):
    """Best-of-``windows`` steady-state step time at full occupancy; the
    decode budget outlives every timed window so no slot retires inside
    one (a draining pool would inflate tok/s with empty-slot steps)."""
    budget = 4 + windows * steps + 2
    sched = Scheduler(model, params, num_slots=B,
                      cache_len=S + budget + 2, paged=True,
                      block_size=BLOCK, mesh=mesh)
    for b in range(B):
        toks = concrete_batch(cfg, 1, S, seed=b)["tokens"]
        sched.submit(Request(uid=b, inputs={"tokens": toks},
                             max_new_tokens=budget))
    for _ in range(4):
        sched.step()                      # admissions + jit warm-up
    plans0 = ttplan.plan_resolutions()
    best = None
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            sched.step()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    assert sched.num_active == B, "slots retired inside a timed window"
    replans = ttplan.plan_resolutions() - plans0
    assert replans == 0, f"{replans} TT plan re-resolutions on the mesh"
    return best / steps, sched


t_step, sched = best_window(k * n)
out["t_step_s"] = t_step
out["replans"] = 0
if n == 1:
    # two-point fit on the single device: T(B) = C_host + B*c gives the
    # host constant and per-token compute the parent needs to derive the
    # per-step collective time of the multi-device rows
    t2, _ = best_window(2 * k)
    out["t_step_2k_s"] = t2

if full:
    COLL = re.compile(r"%(all-reduce|all-gather|reduce-scatter|"
                      r"collective-permute|all-to-all)")
    B = k * n
    toks0 = np.zeros((B, 1), np.int32)
    act = np.ones((B,), bool)
    txt = model.jitted_decode_step_masked(mesh).lower(
        sched.params, sched.cache, jax.numpy.asarray(toks0),
        jax.numpy.asarray(act)).compile().as_text()
    counts = {}
    for m in COLL.finditer(txt):
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    out["collective_ops"] = counts
    out["executables"] = 1                # one partitioned program per step

    # token identity on the mesh: a fixed 4-request workload decoded
    # greedily through the paged and the dense pool must match token for
    # token — and (checked by the parent) match every other device count
    ident = {}
    for paged in (True, False):
        sch = Scheduler(model, params, num_slots=4, cache_len=S + 16,
                        paged=paged, block_size=BLOCK, mesh=mesh)
        for b in range(4):
            toks = concrete_batch(cfg, 1, S, seed=100 + b)["tokens"]
            sch.submit(Request(uid=b, inputs={"tokens": toks},
                               max_new_tokens=12))
        done = sch.run()
        for f in sch.finished:
            done[f.uid] = f
        ident["paged" if paged else "dense"] = [
            [int(t) for t in done[b].tokens] for b in range(4)]
    assert ident["paged"] == ident["dense"], \
        "paged/dense token identity broken on the mesh"
    out["identity_tokens"] = ident["paged"]
print("MESH_SCALING " + json.dumps(out))
'''


def _mesh_scaling(quick: bool) -> dict:
    """Device-count scaling sweep (DESIGN.md §14): the TT smoke model
    served from the paged scheduler over 1/2/4 forced host devices at a
    fixed 4 slots per device (weak scaling — a bigger mesh serves a
    bigger batch at the same per-device KV footprint).

    Each (device count, round) is its own subprocess because
    ``--xla_force_host_platform_device_count`` must be set before jax
    initializes; rounds are interleaved across device counts so ambient
    drift hits every count equally, and the median over rounds is kept.

    This container exposes ONE physical core, so the n partitions of each
    decode step — which a real mesh executes concurrently — run serially
    here, and measured wall time grows with device count by construction.
    The sweep therefore reports both series: ``tok_s_measured`` (raw,
    serialized host) and the headline ``tok_s``, which keeps the measured
    host constant serial and divides the measured device time by n —
    the same first-order deserialization the launch.dryrun methodology
    applies to model pod-scale meshes on this host.  Per-step collective
    time is derived from the single-device two-point fit:
    D(n) = T_n - C_host - B*c."""
    steps, windows = (24, 2) if quick else (48, 4)
    k, S = 4, 16
    rounds = 1 if quick else 3
    counts = (1, 2, 4)
    repo = pathlib.Path(__file__).resolve().parent.parent
    env = _cpu_child_env(repo)

    meas: dict[int, list[dict]] = {n: [] for n in counts}
    for r in range(rounds):
        for n in counts:
            cmd = [sys.executable, "-c", _MESH_WORKER, str(n), str(k),
                   str(S), str(steps), str(windows),
                   "1" if r == 0 else "0"]
            out = subprocess.run(cmd, env=env, cwd=repo,
                                 capture_output=True, text=True)
            if out.returncode != 0:
                raise RuntimeError(
                    f"mesh worker n={n} failed:\n{out.stdout[-2000:]}"
                    f"\n{out.stderr[-4000:]}")
            for line in out.stdout.splitlines():
                if line.startswith("MESH_SCALING "):
                    meas[n].append(json.loads(line[len("MESH_SCALING "):]))
                    break
            else:
                raise RuntimeError(f"no MESH_SCALING line (n={n})")

    med = {n: sorted(m["t_step_s"] for m in meas[n])[len(meas[n]) // 2]
           for n in counts}
    # host constant + per-token compute from the n=1 two-point fit
    t2k = sorted(m["t_step_2k_s"] for m in meas[1])[len(meas[1]) // 2]
    c_tok = max((t2k - med[1]) / k, 0.0)
    c_host = max(med[1] - k * c_tok, 0.0)

    rows = []
    for n in counts:
        first = meas[n][0]
        t = med[n]
        coll_s = max(t - c_host - k * n * c_tok, 0.0) if n > 1 else 0.0
        t_model = c_host + (t - c_host) / n
        rows.append({
            "devices": n, "slots": k * n, "tokens_per_step": k * n,
            "t_step_ms_measured": round(t * 1e3, 4),
            "tok_s_measured": round(k * n / t, 1),
            "per_step_collective_ms": round(coll_s * 1e3, 4),
            "collective_ops": first.get("collective_ops", {}),
            "replans": first["replans"],
            "tok_s": round(k * n / t_model, 1)})

    # identity: paged == dense inside each worker (asserted there), and
    # the same workload decodes identically at every device count
    ident = [meas[n][0]["identity_tokens"] for n in counts]
    if not all(i == ident[0] for i in ident):
        raise AssertionError("decode tokens differ across device counts")
    tok_s = [r["tok_s"] for r in rows]
    if not all(a < b for a, b in zip(tok_s, tok_s[1:])):
        raise AssertionError(
            f"mesh scaling not monotonic: tok/s {tok_s} over {counts} "
            f"devices")

    print("\nmesh scaling (deepseek_7b tt, paged pool, "
          f"{k} slots/device, {rounds} round(s)):")
    for r in rows:
        print(row(f"{r['devices']} dev", f"B={r['slots']}",
                  f"{r['tok_s_measured']:.0f} tok/s measured",
                  f"{r['tok_s']:.0f} tok/s deserialized",
                  f"coll {r['per_step_collective_ms']:.2f} ms/step"))
    return {
        "arch": "deepseek_7b", "mode": "tt", "pool": "paged",
        "slots_per_device": k, "prompt_len": S, "steps": steps,
        "rounds": rounds, "host_physical_cores": os.cpu_count() or 1,
        "host_ms_per_step": round(c_host * 1e3, 4),
        "compute_ms_per_token": round(c_tok * 1e3, 5),
        "method": (
            "weak scaling, one subprocess per (devices, round), median "
            "over interleaved rounds; tok_s keeps the measured host "
            "constant serial and divides measured device time by the "
            "device count (this host executes all partitions on one "
            "physical core); tok_s_measured is the raw serialized wall "
            "clock; per_step_collective_ms = T_n - host - B*compute"),
        "rows": rows, "tok_s": tok_s, "monotonic": True,
        "identity": {"paged_equals_dense_on_mesh": True,
                     "tokens_identical_across_device_counts": True}}


def _pct(xs: list[float], p: float) -> float:
    xs = sorted(xs)
    if len(xs) == 1:
        return xs[0]
    k = (len(xs) - 1) * p / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def _ttft_adversary(quick: bool) -> dict:
    """Long-prompt adversary (DESIGN.md §15): one multi-thousand-token
    prompt lands in a pool of short decoders, with more short requests
    queued behind it.  Monolithic admission prefills the whole adversary
    inside one scheduler step, so every short request behind it inherits
    that full prefill in its time-to-first-token; chunked admission slices
    the adversary into ``chunk_size`` pieces metered by ``prefill_budget``
    and the shorts' first tokens come out after their own (single) chunk.
    Reports p50/p95 TTFT of the trailing shorts, both modes, post-compile
    (an identical throwaway pass warms every jit entry first)."""
    long_len = 1024 if quick else 4096
    chunk, budget = 64, 128            # 2 lanes: adversary + one short
    n_short, S_short, steps = 4, 16, 24
    cfg = get_config("deepseek_7b", "smoke")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache_len = long_len + steps + 2
    slots = 2 + 1 + n_short            # decoders + adversary + shorts

    def workload(seed0):
        mk = lambda n, s: concrete_batch(cfg, 1, n, seed=s)["tokens"]
        return (
            [Request(uid=seed0 + i, inputs={"tokens": mk(S_short, seed0 + i)},
                     max_new_tokens=steps) for i in range(2)],
            Request(uid=seed0 + 50, inputs={"tokens": mk(long_len, seed0)},
                    max_new_tokens=steps),
            [Request(uid=seed0 + 100 + i,
                     inputs={"tokens": mk(S_short, seed0 + 100 + i)},
                     max_new_tokens=steps) for i in range(n_short)])

    def run_mode(chunked, seed0):
        kw = (dict(chunk_prefill=True, chunk_size=chunk,
                   prefill_budget=budget) if chunked else {})
        sched = Scheduler(model, params, num_slots=slots,
                          cache_len=cache_len, paged=True,
                          block_size=BLOCK, **kw)
        decoders, adversary, shorts = workload(seed0)
        for r in decoders:
            sched.submit(r)
        sched.step()                   # decoders admitted and decoding
        sched.step()
        sched.submit(adversary)        # FIFO: the adversary ranks first,
        for r in shorts:               # the shorts queue behind it
            sched.submit(r)
        finished = sched.run()
        ttfts = [finished[r.uid].first_token_time
                 - finished[r.uid].submit_time for r in shorts]
        return ttfts, sched.stats()

    out = {}
    for mode, chunked in (("monolithic", False), ("chunked", True)):
        run_mode(chunked, seed0=1000)            # warm every jit entry
        ttfts, st = run_mode(chunked, seed0=2000)
        out[mode] = {"ttft_p50_s": _pct(ttfts, 50),
                     "ttft_p95_s": _pct(ttfts, 95),
                     "ttft_max_s": max(ttfts)}
        if chunked:
            out[mode]["prefill_chunks"] = st["prefill_chunks"]
    red = out["monolithic"]["ttft_p95_s"] / out["chunked"]["ttft_p95_s"]
    if red <= 1.0:
        raise AssertionError(
            f"chunked prefill did not improve p95 TTFT under the "
            f"long-prompt adversary: {out}")
    out.update({
        "arch": "deepseek_7b", "long_prompt": long_len,
        "n_short": n_short, "short_prompt": S_short, "steps": steps,
        "chunk_size": chunk, "prefill_budget": budget, "block": BLOCK,
        "p95_ttft_reduction": round(red, 2)})
    print(f"\nlong-prompt adversary ({long_len}-token prompt, {n_short} "
          f"trailing shorts): p95 TTFT "
          f"{out['monolithic']['ttft_p95_s']*1e3:.1f}ms monolithic → "
          f"{out['chunked']['ttft_p95_s']*1e3:.1f}ms chunked "
          f"({red:.2f}x)")
    return out


def run(quick: bool = False) -> None:
    # This bench is the CPU rehearsal of the serving path.  It pins itself
    # and every child process to the CPU: on a TPU host a parent that has
    # touched the chip would hold it while its children start.
    jax.config.update("jax_platforms", "cpu")
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "bench_serve_tt is the CPU rehearsal, but JAX is already "
            "running on the TPU in this process — run it with "
            "JAX_PLATFORMS=cpu")
    S, steps = 16, (8 if quick else 16)
    slot_counts = [2] if quick else [1, 2, 4, 8]
    archs = ["deepseek_7b"] if quick else ["deepseek_7b", "qwen3_32b",
                                           "gemma3_4b"]
    header("model-level serve: dense vs TT × fixed vs dense/paged pools",
           ["arch", "mode", "slots", "fixed_tok_s", "sched_tok_s",
            "paged_tok_s", "paged_over_sched", "pool_MB_dense",
            "pool_MB_paged"])
    records = []
    for arch in archs:
        base = get_config(arch, "smoke")
        variants = {
            "dense": dataclasses.replace(
                base, tt=dataclasses.replace(base.tt, enabled=False)),
            "tt": dataclasses.replace(
                base, tt=TTConfig(enabled=True, families=("ffn", "attn"),
                                  rank=4, min_factor=2)),
        }
        for mode, cfg in variants.items():
            model = build(cfg)
            params = model.init(jax.random.PRNGKey(0))
            n_params = model.num_params()
            for B in slot_counts:
                tps_f = _fixed_throughput(model, params, B, S, steps)
                tps_s, st_s = _sched_throughput(model, params, B, S, steps,
                                                paged=False)
                tps_p, st_p = _sched_throughput(model, params, B, S, steps,
                                                paged=True)
                mb_s = st_s["kv_pool_bytes"] / 1e6
                mb_p = st_p["kv_pool_bytes"] / 1e6
                print(row(arch, mode, B, f"{tps_f:.1f}", f"{tps_s:.1f}",
                          f"{tps_p:.1f}", f"{tps_p/tps_s:.2f}",
                          f"{mb_s:.2f}", f"{mb_p:.2f}"))
                records.append({
                    "arch": arch, "mode": mode, "slots": B,
                    "params": n_params, "prompt_len": S, "steps": steps,
                    "fixed_tok_s": tps_f, "sched_tok_s": tps_s,
                    "paged_tok_s": tps_p,
                    "dense_pool_bytes": st_s["kv_pool_bytes"],
                    "paged_pool_bytes": st_p["kv_pool_bytes"],
                    "paged_block_high_water": st_p["block_high_water"],
                    "paged_block_size": st_p["block_size"]})

    # shared-prefix workload: measured prefill-time reduction from reuse
    # (the prefix is long relative to the smoke model so the saved matmuls
    # dominate the per-admission dispatch overhead)
    px_arch = "deepseek_7b"
    px_len = 128 if quick else 384
    model = build(get_config(px_arch, "smoke"))
    params = model.init(jax.random.PRNGKey(0))
    px = _prefix_workload(model, params, n_req=2 if quick else 6,
                          prefix_len=px_len, tail=16, steps=2)
    print(f"\nshared-prefix workload ({px_arch}, {px_len}-token prefix): "
          f"admission {px['off']['wall_s']*1e3:.0f}ms → "
          f"{px['on']['wall_s']*1e3:.0f}ms "
          f"({px['speedup']:.2f}x), hit rate {px['on']['hit_rate']:.2f}, "
          f"{px['on']['prefill_tokens_skipped']} prefill tokens skipped")
    # cold vs warm process start→first token (persistent compile cache)
    cold_start = _cold_start()
    # chunked-vs-monolithic TTFT under a long-prompt adversary (§15)
    ttft_adversary = _ttft_adversary(quick)
    # device-count scaling over forced host meshes (DESIGN.md §14)
    mesh_scaling = _mesh_scaling(quick)

    RESULTS.mkdir(exist_ok=True)
    out = RESULTS / "BENCH_serve.json"
    out.write_text(json.dumps(
        {"backend": jax.default_backend(), "records": records,
         "prefix_workload": {"arch": px_arch, "prefix_len": px_len,
                             "block": BLOCK, **px},
         "cold_start": cold_start,
         "ttft_adversary": ttft_adversary,
         "mesh_scaling": mesh_scaling}, indent=1))
    print(f"wrote {out}")


if __name__ == "__main__":
    run()
