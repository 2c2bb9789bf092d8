"""Serving launcher.

Fixed-batch mode (default): prefill a batch of synthetic prompts, decode N
tokens, reporting compile time and steady-state throughput *separately*
(the first generate call pays trace+compile; the second is the number that
scales).

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
      --variant smoke --batch 4 --prompt-len 64 --steps 32

Continuous-batching simulation mode (--arrival-rate): requests arrive as a
Poisson process into the slot-pool scheduler; reports steady-state tok/s
and p50/p95 per-request latency, with compile time excluded via a warm-up
request.  ``--paged`` switches the pool to the block-paged KV cache
(DESIGN.md §7) and reports KV-pool bytes, the block high-water mark and
the prefix-cache hit rate.

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b \
      --arrival-rate 4 --max-requests 16 --slots 4 --prompt-len 16 \
      --steps 8 --paged

Chunked prefill (--chunk-prefill, DESIGN.md §15): prompts stream into the
pool ``--chunk-size`` tokens at a time *inside* the fused decode step —
decoding requests keep emitting tokens while a long prompt prefills, so
p95 TTFT stops being hostage to the longest prompt in the queue.
``--prefill-budget`` caps prefill tokens per step (the prefill-vs-decode
SLO knob).  Output is token-identical to monolithic prefill.

Streaming serving (--serve / --serve-smoke, DESIGN.md §15): an HTTP/SSE
front-end (stdlib-only) over the async StreamEngine — POST /generate
streams per-token events, GET /stream/<uid>?from=N resumes a dropped
stream (journal-aware with --durable/--restore), POST /shutdown drains.

Prefix-reuse smoke (--prefix-smoke): two requests sharing a long prompt
prefix through the paged scheduler; asserts the second request shares >= 1
resident block and skips the covered prefill compute.

Fault-injection smoke (--fault-smoke): a seeded ``serving.faults``
FaultPlan (alloc failures, admission holds, a cancel, a live resize, a
simulated restart) over a mixed-priority workload; asserts zero leaked
blocks, zero TT plan re-resolutions and survivor token identity
(DESIGN.md §11).

Durability (DESIGN.md §13): ``--compile-cache DIR`` enables the
persistent XLA compilation cache (a restarted process re-jits nothing;
``--assert-cache-hits`` makes CI fail if it does); ``--first-token``
prints a machine-readable ``COLD_START`` line with the process-start →
first-token time (run it twice against one cache dir to measure cold
vs. warm); ``--durable DIR`` wraps the scheduler in the journal +
snapshot pipeline so Ctrl-C (and kill -9) preserve in-flight work,
resumable with ``--restore``; ``--durability-smoke`` is the CI drill for
kill/truncate/bit-flip recovery.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

# captured before the jax import below, so --first-token's "process
# start → first token" includes jax/XLA startup and every compile —
# exactly the costs the persistent compilation cache amortises
_PROC_T0 = time.perf_counter()

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import build, get_config
from repro.configs.base import TTConfig
from repro.configs.shapes import concrete_batch
from repro.kernels import plan as ttplan
from repro.serving.engine import generate_fixed
from repro.serving.scheduler import Request, Scheduler

from .cache import cache_entries, enable_compile_cache


def _percentile(xs: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else float("nan")


def _make_sched(model, params, args, cache_len):
    return Scheduler(model, params, num_slots=args.slots,
                     cache_len=cache_len, eos_id=args.eos_id,
                     key=jax.random.PRNGKey(args.seed + 1),
                     paged=args.paged, block_size=args.block_size,
                     num_blocks=args.num_blocks, mesh=args.mesh_obj,
                     chunk_prefill=args.chunk_prefill,
                     chunk_size=args.chunk_size,
                     prefill_budget=args.prefill_budget)


def _print_pool_stats(sched) -> None:
    st = sched.stats()
    print(f"kv pool: {st['kv_pool_bytes'] / 1e6:.2f} MB", end="")
    if sched.paged:
        print(f" | blocks: {st['num_blocks']}x{st['block_size']} tokens, "
              f"high-water {st['block_high_water']} "
              f"| prefix hit rate {st['prefix_hit_rate']:.2f} "
              f"({st['prefill_tokens_skipped']} prefill tokens skipped)")
    else:
        print()
    steps, host = st["host_steps"], st["host_s"]
    if steps:
        split = ", ".join(f"{k} {v / steps * 1e3:.2f}"
                          for k, v in host.items() if k != "step")
        print(f"host phases, ms per step: step "
              f"{host['step'] / steps * 1e3:.2f} ({split}); longest step "
              f"{st['host_max_s']['step'] * 1e3:.1f} ms")
    n = st["first_tokens"]
    if n:
        print(f"first token, mean of {n}: queue wait "
              f"{st['ttft_queue_s'] / n * 1e3:.1f} ms, prefill "
              f"{st['ttft_prefill_s'] / n * 1e3:.1f} ms")


def simulate(model, params, args) -> dict:
    """Poisson-arrival continuous-batching simulation (wall-clock driven)."""
    steps = args.steps
    cache_len = args.prompt_len + steps
    sched = _make_sched(model, params, args, cache_len)

    def req(uid, seed):
        toks = concrete_batch(model.cfg, 1, args.prompt_len,
                              seed=seed)["tokens"]
        return Request(uid=uid, inputs={"tokens": toks},
                       max_new_tokens=steps,
                       temperature=args.temperature, top_k=args.top_k)

    # warm-up: one throwaway request compiles prefill, splice, the masked
    # decode step and the pick — all shapes the simulation will reuse
    t0 = time.perf_counter()
    sched.submit(req(-1, args.seed + 999))
    sched.run()
    compile_s = time.perf_counter() - t0
    sched.reset_stats()                    # warm-up out of steady-state
    # every TT plan is resolved at model build / warm-up; the steady-state
    # run must never plan again (DESIGN.md §10)
    plans_warm = ttplan.plan_resolutions()

    if args.durable:
        from repro.serving.durable import DurableScheduler
        if args.restore:
            # the warm-up already compiled every program on this Model, so
            # the recovered scheduler (same model, fresh state) re-jits
            # nothing while it drains the restored requests
            sched = DurableScheduler.recover(
                args.durable, model, params, rebase_clock=True,
                snapshot_every=args.snapshot_every, log=print)
        else:
            sched = DurableScheduler(sched, args.durable,
                                     snapshot_every=args.snapshot_every)

    rng = np.random.default_rng(args.seed)
    arrivals = np.cumsum(rng.exponential(1.0 / args.arrival_rate,
                                         size=args.max_requests))
    start = time.perf_counter()
    # --restore drains the recovered requests only: re-submitting the
    # synthetic workload would collide with the restored uids
    i = args.max_requests if (args.durable and args.restore) else 0
    interrupted = False
    preserved = False
    try:
        while i < args.max_requests or not sched.idle:
            now = time.perf_counter() - start
            while i < args.max_requests and arrivals[i] <= now:
                sched.submit(req(i, args.seed + i),
                             submit_time=start + arrivals[i])
                i += 1
            if sched.idle:                  # ahead of the arrival process
                time.sleep(max(0.0,
                               arrivals[i] - (time.perf_counter() - start)))
                continue
            sched.step()
    except KeyboardInterrupt:
        interrupted = True
        if args.durable:
            # graceful shutdown == crash recovery entry point: checkpoint
            # the live state (snapshot generation + journal rotation) and
            # keep in-flight work — a later --restore run resumes it
            gen = sched.checkpoint()
            sched.close()
            preserved = True
            print(f"\ninterrupted — state checkpointed to {args.durable} "
                  f"(generation {gen}, {len(sched.queue)} queued, "
                  f"{sched.num_active} active); resume with --restore")
        else:
            # graceful drain: retire everything still pending as
            # "cancelled" (partial tokens kept) so blocks/slots free and
            # the report below still prints — flagged partial — exit 0
            for q in list(sched.queue):
                sched.cancel(q.req.uid)
            for s in list(sched.slots):
                if s is not None:
                    sched.cancel(s.uid)
    if args.durable and not preserved:
        sched.checkpoint()                 # final snapshot on a clean drain
        sched.close()
    wall = time.perf_counter() - start
    finished = list(sched.finished)

    lats = [f.finish_time - f.submit_time for f in finished]
    # TTFT (submit → first token: queueing + prefill) and inter-token
    # latency (per-token decode cadence after the first) are separate
    # SLOs — chunked prefill trades the one against the other, so they
    # are reported apart (ISSUE 10 satellite)
    ttfts = [f.first_token_time - f.submit_time for f in finished
             if f.first_token_time is not None]
    itls = [(f.finish_time - f.first_token_time) / (len(f.tokens) - 1)
            for f in finished
            if f.first_token_time is not None and len(f.tokens) > 1]
    tok_s = sched.tokens_out / wall if wall > 0 else float("nan")
    p50, p95 = _percentile(lats, 50), _percentile(lats, 95)
    ttft50, ttft95 = _percentile(ttfts, 50), _percentile(ttfts, 95)
    itl50, itl95 = _percentile(itls, 50), _percentile(itls, 95)
    partial = " (PARTIAL — interrupted)" if interrupted else ""
    chunked = (f" chunk={sched.chunk_size}x{sched.chunk_lanes}"
               if args.chunk_prefill else "")
    print(f"arch={model.cfg.name} slots={args.slots} "
          f"arrival_rate={args.arrival_rate}/s requests={len(finished)} "
          f"prompt={args.prompt_len} max_new={steps} "
          f"pool={'paged' if args.paged else 'dense'}{chunked}{partial}")
    print(f"compile (warm-up request): {compile_s:.2f}s — excluded below")
    print(f"steady-state: {sched.tokens_out} tokens in {wall:.2f}s "
          f"({tok_s:.1f} tok/s), decode steps={sched.steps_run}")
    print(f"per-request latency: p50={p50*1e3:.1f}ms p95={p95*1e3:.1f}ms")
    print(f"ttft: p50={ttft50*1e3:.1f}ms p95={ttft95*1e3:.1f}ms | "
          f"inter-token: p50={itl50*1e3:.1f}ms p95={itl95*1e3:.1f}ms")
    if args.chunk_prefill:
        print(f"prefill chunks executed: {sched.prefill_chunks} "
              f"(budget {sched.prefill_budget} tok/step)")
    _print_pool_stats(sched)
    if interrupted and sched.paged and not preserved:
        sched.allocator.assert_quiescent()  # interrupt must not leak blocks
    replans = ttplan.plan_resolutions() - plans_warm
    print(f"plan resolutions during steady state: {replans} "
          f"(model plans: {len(model.plan_book)})")
    if args.assert_no_replan and replans != 0:
        raise AssertionError(
            f"{replans} TT plan resolutions during the steady-state run — "
            "serving must execute build-time plans only")
    return {"scheduler": sched, "finished": finished, "tok_per_s": tok_s,
            "p50_s": p50,
            "p95_s": p95, "ttft_p50_s": ttft50, "ttft_p95_s": ttft95,
            "itl_p50_s": itl50, "itl_p95_s": itl95,
            "compile_s": compile_s, "replans": replans,
            "interrupted": interrupted}


def prefix_smoke(model, params, args) -> dict:
    """Prefix-reuse smoke (CI): two requests whose prompts share a
    ``--prefix-len``-token prefix through the paged scheduler.  The second
    admission must find the prefix blocks resident — sharing >= 1 block,
    skipping the covered prefill compute — and both outputs must match the
    dense-scheduler reference token-for-token."""
    from repro.serving.engine import generate_fixed

    P, tail, steps = args.prefix_len, 16, args.steps
    cache_len = P + tail + steps
    prefix = concrete_batch(model.cfg, 1, P, seed=args.seed)["tokens"]
    prompts = [
        jnp.concatenate(
            [prefix, concrete_batch(model.cfg, 1, tail,
                                    seed=args.seed + 1 + i)["tokens"]], 1)
        for i in range(2)]
    sched = _make_sched(model, params, args, cache_len)
    if not sched.paged or not sched.prefix_cache:
        raise SystemExit("--prefix-smoke requires --paged and a "
                         "prefix-shareable arch (full attention / MLA)")
    t_admit = []
    for uid, toks in enumerate(prompts):
        t0 = time.perf_counter()
        sched.submit(Request(uid=uid, inputs={"tokens": toks},
                             max_new_tokens=steps))
        sched.step()                      # admission (+ first decode step)
        t_admit.append(time.perf_counter() - t0)
    out = sched.run()
    for f in sched.finished:
        out[f.uid] = f
    st = sched.stats()
    shared_blocks = st["prefix_hit_tokens"] // sched.block
    print(f"arch={model.cfg.name} prefix={P} tail={tail} "
          f"block={sched.block}")
    print(f"admission wall: first={t_admit[0]*1e3:.1f}ms "
          f"(cold, compiles) second={t_admit[1]*1e3:.1f}ms")
    print(f"prefix: {shared_blocks} shared blocks, "
          f"{st['prefill_tokens_skipped']} prefill tokens skipped, "
          f"hit rate {st['prefix_hit_rate']:.2f}")
    _print_pool_stats(sched)
    if shared_blocks < 1 or st["prefill_tokens_skipped"] < P - sched.block:
        raise AssertionError(
            f"prefix reuse failed: {shared_blocks} shared blocks, "
            f"{st['prefill_tokens_skipped']} tokens skipped (prefix {P})")
    for uid, toks in enumerate(prompts):
        ref = generate_fixed(model, params,
                             {"tokens": toks, "cache_len": cache_len},
                             steps=steps)
        if out[uid].tokens.tolist() != np.asarray(
                ref.tokens)[0].tolist():
            raise AssertionError(f"request {uid}: paged prefix-reuse "
                                 "output diverged from the dense reference")
    print("prefix-reuse smoke OK (outputs token-identical to dense)")
    return {"shared_blocks": shared_blocks, **st}


def fault_smoke(model, params, args) -> dict:
    """Fault-injection smoke (CI): a seeded FaultPlan — forced alloc
    failures, an admission hold, one mid-stream cancel, one live resize
    and one simulated restart — over a synthetic mixed-priority workload,
    asserting the full invariant suite (``serving.faults``): zero leaked
    blocks, zero plan re-resolutions, and every surviving request's
    tokens bit-identical to an uninterrupted run."""
    from repro.serving.faults import FaultPlan, run_with_faults

    steps = args.steps
    cache_len = args.prompt_len + steps
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed + 1)
    reqs = []
    for uid in range(args.max_requests):
        toks = concrete_batch(model.cfg, 1, args.prompt_len,
                              seed=args.seed + uid)["tokens"]
        reqs.append(Request(
            uid=uid, inputs={"tokens": toks}, max_new_tokens=steps,
            temperature=args.temperature, top_k=args.top_k,
            key=jax.random.fold_in(key, uid),
            priority=int(rng.integers(0, 3)),
            # one tight TTL exercises the deadline/expiry path (virtual
            # step clock: deadline_s is in scheduler steps here)
            deadline_s=3.0 if uid == 0 else None))
    kw = dict(num_slots=args.slots, cache_len=cache_len, eos_id=args.eos_id,
              key=key, paged=args.paged, block_size=args.block_size,
              num_blocks=args.num_blocks,
              chunk_prefill=args.chunk_prefill, chunk_size=args.chunk_size,
              prefill_budget=args.prefill_budget)
    # Poisson arrivals in scheduler steps; the last (high-priority, late)
    # arrival lands mid-stream so the preemption path is exercised too
    arrivals = np.cumsum(rng.poisson(1.0, size=len(reqs))).tolist()
    reqs[-1] = dataclasses.replace(reqs[-1], priority=9, deadline_s=None)
    plan = FaultPlan.random(
        args.seed, horizon=max(4, steps),
        uids=[r.uid for r in reqs[:-1]],    # keep the preemptor alive
        resize_to=(args.slots + 1, None))
    print(f"arch={model.cfg.name} slots={args.slots} "
          f"requests={len(reqs)} pool={'paged' if args.paged else 'dense'}")
    print(f"fault plan: alloc_fail@{sorted(plan.alloc_fail_steps)} "
          f"hold@{sorted(plan.hold_steps)} cancels={list(plan.cancels)} "
          f"resizes={list(plan.resizes)} "
          f"restart@{sorted(plan.restart_steps)} arrivals@{arrivals}")
    rep = run_with_faults(model, params, reqs, plan, sched_kwargs=kw,
                          arrival_steps=arrivals)
    print(f"drained in {rep.steps} steps: restarts={rep.restarts} "
          f"preemptions={rep.preemptions} cancelled={rep.cancelled} "
          f"expired={rep.expired} replans={rep.replans}")
    print(f"fault-injection smoke OK ({len(rep.survivors)} survivors "
          f"token-identical to the uninterrupted run)")
    return {"steps": rep.steps, "restarts": rep.restarts,
            "preemptions": rep.preemptions, "cancelled": rep.cancelled,
            "expired": rep.expired, "survivors": len(rep.survivors)}


def first_token(model, params, args) -> dict:
    """Cold-start probe: one request through the scheduler, reporting
    process start → first decoded token on a machine-readable
    ``COLD_START`` line.  Run twice against one ``--compile-cache`` dir —
    the second (warm) run re-jits nothing and must be faster; CI and
    bench_serve_tt parse the line and assert exactly that."""
    cache_len = args.prompt_len + args.steps
    sched = _make_sched(model, params, args, cache_len)
    toks = concrete_batch(model.cfg, 1, args.prompt_len,
                          seed=args.seed)["tokens"]
    sched.submit(Request(uid=0, inputs={"tokens": toks},
                         max_new_tokens=args.steps,
                         temperature=args.temperature, top_k=args.top_k))
    while sched.tokens_out < 1:
        sched.step()
    t_first = time.perf_counter() - _PROC_T0
    sched.run()                            # drain the rest of the budget
    out = {"arch": model.cfg.name, "prompt_len": args.prompt_len,
           "steps": args.steps,
           "start_to_first_token_s": round(t_first, 4),
           "compile_cache": args.compile_cache,
           "cache_entries": cache_entries(args.compile_cache)}
    print("COLD_START " + json.dumps(out))
    return out


def durability_smoke(model, params, args) -> dict:
    """Durability fault drill (CI, DESIGN.md §13).  Three drills:

    1. kill -9 at a seeded step with the journal + snapshot pipeline on a
       clean store — recovery replays the journal; survivor streams must
       be bit-identical to an uninterrupted run, zero leaked blocks, zero
       plan re-resolutions.
    2. the same kill, but a corruptor truncates / bit-flips the newest
       committed snapshot generation before recovery runs — the
       checksummed fallback must restore the previous generation and
       replay forward across the gap.
    3. store-level: a snapshot whose newest generation is truncated then
       bit-flipped must fall back on load, and a fully-corrupt store must
       raise a clear error — a torn state is never returned.
    """
    import tempfile

    from repro.core import durable
    from repro.serving.faults import (FaultPlan, load_snapshot,
                                      run_with_faults, save_snapshot)

    steps = args.steps
    cache_len = args.prompt_len + steps
    key = jax.random.PRNGKey(args.seed + 1)
    reqs = []
    for uid in range(args.max_requests):
        toks = concrete_batch(model.cfg, 1, args.prompt_len,
                              seed=args.seed + uid)["tokens"]
        reqs.append(Request(uid=uid, inputs={"tokens": toks},
                            max_new_tokens=steps,
                            temperature=args.temperature, top_k=args.top_k,
                            key=jax.random.fold_in(key, uid)))
    kw = dict(num_slots=args.slots, cache_len=cache_len, eos_id=args.eos_id,
              key=key, paged=args.paged, block_size=args.block_size,
              num_blocks=args.num_blocks,
              chunk_prefill=args.chunk_prefill, chunk_size=args.chunk_size,
              prefill_budget=args.prefill_budget)
    plan = FaultPlan.random(args.seed, horizon=max(4, steps),
                            n_alloc_fail=0, n_hold=0, n_cancel=0,
                            with_restart=False, with_kill=True)
    print(f"arch={model.cfg.name} slots={args.slots} requests={len(reqs)} "
          f"pool={'paged' if args.paged else 'dense'} "
          f"kill@{sorted(plan.kill_steps)}")

    with tempfile.TemporaryDirectory() as d:
        rep = run_with_faults(model, params, reqs, plan, sched_kwargs=kw,
                              durable_dir=d, snapshot_every=2)
    assert rep.kills == 1, rep
    print(f"kill drill OK: drained in {rep.steps} steps, "
          f"{len(rep.survivors)} survivors token-identical after recovery")

    rng = np.random.default_rng(args.seed + 7)
    corruptions: list[str] = []

    def corruptor(root, step):
        gens = durable.committed_generations(root)
        if len(gens) < 2:
            return                        # keep one good generation
        p = os.path.join(root, f"gen_{gens[-1]:08d}", "arrays.bin")
        size = os.path.getsize(p)
        if rng.integers(0, 2) == 0:
            with open(p, "r+b") as f:
                f.truncate(int(rng.integers(0, size)))
            corruptions.append(f"truncate gen {gens[-1]}")
        else:
            off = int(rng.integers(0, size))
            with open(p, "r+b") as f:
                f.seek(off)
                b = f.read(1)
                f.seek(off)
                f.write(bytes([b[0] ^ (1 << int(rng.integers(0, 8)))]))
            corruptions.append(f"bit-flip gen {gens[-1]}")

    with tempfile.TemporaryDirectory() as d:
        rep2 = run_with_faults(model, params, reqs, plan, sched_kwargs=kw,
                               baseline=rep.baseline, durable_dir=d,
                               snapshot_every=2, corruptor=corruptor)
    assert rep2.kills == 1, rep2
    print(f"corrupting-kill drill OK ({corruptions or 'nothing to corrupt'})"
          f": recovery fell back past the damage, survivors identical")

    with tempfile.TemporaryDirectory() as d:
        snap1 = {"version": 0, "gen": np.asarray([1], np.int32)}
        snap2 = {"version": 0, "gen": np.asarray([2], np.int32)}
        save_snapshot(d, snap1)
        save_snapshot(d, snap2)
        p = os.path.join(d, "gen_00000002", "arrays.bin")
        with open(p, "r+b") as f:
            f.truncate(2)
        assert int(load_snapshot(d)["gen"][0]) == 1   # fell back
        p1 = os.path.join(d, "gen_00000001", "arrays.bin")
        with open(p1, "r+b") as f:
            f.seek(0)
            b = f.read(1)
            f.seek(0)
            f.write(bytes([b[0] ^ 1]))
        try:
            load_snapshot(d)
            raise AssertionError("fully-corrupt store must raise")
        except durable.CorruptGenerationError:
            pass
    print("store drill OK: truncation falls back, full corruption raises "
          "— a torn state is never returned")
    print("durability smoke OK")
    return {"kills": rep.kills + rep2.kills, "corruptions": corruptions,
            "survivors": len(rep.survivors)}


def serve_mode(model, params, args) -> dict:
    """HTTP/SSE serving (DESIGN.md §15): a StreamEngine step loop behind
    the stdlib SSE front-end.  ``--durable DIR`` journals every
    submit/retire (``--restore`` recovers after a crash, with in-flight
    token streams replayable through GET /stream/<uid>?from=N — the
    journal-aware client reconnect)."""
    from repro.serving.engine import StreamEngine
    from repro.serving.server import make_server

    cache_len = args.prompt_len + args.steps
    sched = _make_sched(model, params, args, cache_len)
    if args.durable:
        from repro.serving.durable import DurableScheduler
        if args.restore:
            sched = DurableScheduler.recover(
                args.durable, model, params, rebase_clock=True,
                snapshot_every=args.snapshot_every, log=print)
        else:
            sched = DurableScheduler(sched, args.durable,
                                     snapshot_every=args.snapshot_every)
    eng = StreamEngine(sched)
    srv = make_server(eng, host=args.host, port=args.port, quiet=False)
    host, port = srv.server_address[:2]
    print(f"serving on http://{host}:{port} — POST /generate, "
          f"GET /stream/<uid>?from=N, GET /stats, POST /shutdown "
          f"(cache_len={cache_len}, "
          f"chunked={'on' if args.chunk_prefill else 'off'})")
    try:
        srv.serve_forever()
        print("shutdown requested — draining")
    except KeyboardInterrupt:
        print("\ninterrupted — draining")
    finally:
        srv.server_close()
        eng.close()
    st = eng.stats()
    print(f"served {st['requests_done']} requests, "
          f"{st['tokens_out']} tokens")
    return st


def serve_smoke(model, params, args) -> dict:
    """CI streaming smoke: an in-process SSE server, two *overlapping*
    streaming requests (per-token events must arrive in order and
    interleave across requests), a mid-stream reconnect replay from an
    arbitrary index, and a graceful POST /shutdown."""
    import http.client
    import threading

    from repro.serving.engine import StreamEngine
    from repro.serving.server import make_server

    steps = args.steps
    cache_len = args.prompt_len + steps
    sched = _make_sched(model, params, args, cache_len)
    eng = StreamEngine(sched)
    plans0 = ttplan.plan_resolutions()    # everything resolved at build
    srv = make_server(eng)
    port = srv.server_address[1]
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()

    def events(resp):
        buf = b""
        while True:
            chunk = resp.read1(4096)
            if not chunk:
                return
            buf += chunk
            while b"\n\n" in buf:
                raw, buf = buf.split(b"\n\n", 1)
                for line in raw.split(b"\n"):
                    if line.startswith(b"data: "):
                        yield json.loads(line[6:])

    def client(uid, toks, out):
        c = http.client.HTTPConnection("127.0.0.1", port)
        c.request("POST", "/generate",
                  json.dumps({"tokens": toks, "max_new_tokens": steps,
                              "uid": uid}),
                  {"Content-Type": "application/json"})
        r = c.getresponse()
        assert r.status == 200, r.status
        for ev in events(r):
            out.append((time.perf_counter(), ev))
            if "done" in ev:
                break
        c.close()

    prompts = [concrete_batch(model.cfg, 1, args.prompt_len,
                              seed=args.seed + i)["tokens"][0].tolist()
               for i in range(2)]
    outs = [[], []]
    threads = [threading.Thread(target=client, args=(i, prompts[i],
                                                     outs[i]))
               for i in range(2)]
    threads[0].start()
    time.sleep(0.02)
    threads[1].start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "streaming client timed out"
    for uid, out in enumerate(outs):
        assert out[-1][1].get("done") == "length", out[-1]
        idx = [ev["i"] for _, ev in out[:-1]]
        assert idx == list(range(steps)), \
            f"uid {uid}: events out of order: {idx}"
    # the two token streams must overlap in wall time (continuous
    # batching, not serial): each starts before the other finishes
    starts = [out[0][0] for out in outs]
    ends = [out[-1][0] for out in outs]
    assert max(starts) < min(ends), "request streams did not overlap"
    print(f"overlapping streams OK: 2 x {steps} ordered per-token events")

    # reconnect mid-stream: replay uid 0 from an arbitrary index
    frm = max(1, steps // 2)
    c = http.client.HTTPConnection("127.0.0.1", port)
    c.request("GET", f"/stream/0?from={frm}")
    replay = []
    for ev in events(c.getresponse()):
        replay.append(ev)
        if "done" in ev:
            break
    c.close()
    want = [ev["token"] for _, ev in outs[0][frm:-1]]
    got = [ev["token"] for ev in replay[:-1]]
    assert got == want and replay[-1]["done"] == "length", (replay, want)
    print(f"reconnect OK: replayed {len(got)} events from index {frm}")

    c = http.client.HTTPConnection("127.0.0.1", port)
    c.request("GET", "/stats")
    st = json.loads(c.getresponse().read())
    c.close()
    c = http.client.HTTPConnection("127.0.0.1", port)
    c.request("POST", "/shutdown", "{}")
    assert json.loads(c.getresponse().read())["ok"]
    c.close()
    th.join(timeout=30)
    assert not th.is_alive(), "server did not shut down"
    eng.close()
    replans = ttplan.plan_resolutions() - plans0
    print(f"graceful shutdown OK; plan resolutions during serving: "
          f"{replans}")
    if args.assert_no_replan and replans != 0:
        raise AssertionError(
            f"{replans} TT plan resolutions during streaming serving")
    print("streaming smoke OK")
    return {"requests": 2, "steps": steps, "replans": replans, **st}


def fixed(model, params, args) -> dict:
    batch = concrete_batch(model.cfg, args.batch, args.prompt_len,
                           seed=args.seed)
    batch = dict(batch, cache_len=args.prompt_len + args.steps)
    key = jax.random.PRNGKey(args.seed + 1)

    t0 = time.perf_counter()
    res = generate_fixed(model, params, batch, steps=args.steps,
                         temperature=args.temperature, key=key)
    jax.block_until_ready(res.tokens)
    cold = time.perf_counter() - t0
    plans_warm = ttplan.plan_resolutions()     # all resolved by now
    t0 = time.perf_counter()
    res = generate_fixed(model, params, batch, steps=args.steps,
                         temperature=args.temperature, key=key)
    jax.block_until_ready(res.tokens)
    warm = time.perf_counter() - t0
    replans = ttplan.plan_resolutions() - plans_warm

    toks = args.batch * args.steps
    compile_s = max(cold - warm, 0.0)
    print(f"arch={model.cfg.name} batch={args.batch} "
          f"prompt={args.prompt_len} decode={args.steps}")
    print(f"compile: {compile_s:.2f}s (cold {cold:.2f}s − warm {warm:.2f}s)")
    print(f"steady-state: {toks} tokens in {warm:.2f}s "
          f"({toks/warm:.1f} tok/s incl. prefill, excl. compile)")
    print("sample tokens[0]:", res.tokens[0].tolist())
    print(f"plan resolutions during warm run: {replans} "
          f"(model plans: {len(model.plan_book)})")
    if args.assert_no_replan and replans != 0:
        raise AssertionError(
            f"{replans} TT plan resolutions during the warm run — "
            "serving must execute build-time plans only")
    return {"tokens": res.tokens, "tok_per_s": toks / warm,
            "compile_s": compile_s, "replans": replans}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--steps", type=int, default=32,
                    help="decode budget (max_new_tokens per request)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--tt", default=None)
    ap.add_argument("--tt-rank", type=int, default=16)
    ap.add_argument("--tt-backend", default="auto",
                    choices=list(ttplan.BACKENDS),
                    help="TT chain backend; auto runs the Pallas kernels "
                         "(fused where VMEM admits the chain)")
    ap.add_argument("--tt-autotune", default="cached",
                    choices=["off", "cached", "measure"])
    ap.add_argument("--tt-weights", default="fp32",
                    choices=["fp32", "int8"],
                    help="resident TT core dtype; int8 quantizes the "
                         "checkpoint offline and serves the int8-resident "
                         "kernel path (DESIGN.md §8)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="per-request top-k sampling filter (0 = off)")
    ap.add_argument("--mesh", type=int, default=None,
                    help="serve over an N-device (1, N) mesh (DESIGN.md "
                         "§14): params/KV pool sharded by data placement, "
                         "decode stays one collective-aware executable.  "
                         "On CPU the devices must exist before jax starts "
                         "— launch with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    # continuous-batching simulation
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="Poisson arrival rate (req/s); enables simulation")
    ap.add_argument("--max-requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=None,
                    help="slot-pool size (default: --batch)")
    ap.add_argument("--eos-id", type=int, default=None)
    # block-paged KV cache (DESIGN.md §7)
    ap.add_argument("--paged", action="store_true",
                    help="serve from the block-paged KV pool with "
                         "hash-based prefix reuse")
    ap.add_argument("--block-size", type=int, default=64,
                    help="tokens per KV block (--paged)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="arena blocks (default: slots x ceil(cache/block) "
                         "— admission is by free blocks, not slots)")
    # chunked prefill fused into the decode step (DESIGN.md §15)
    ap.add_argument("--chunk-prefill", action="store_true",
                    help="prefill prompts in fixed-size chunks INSIDE the "
                         "fused decode step (one traced program): decoding "
                         "requests keep emitting tokens while a long "
                         "prompt streams in, cutting p95 TTFT under mixed "
                         "workloads")
    ap.add_argument("--chunk-size", type=int, default=64,
                    help="prompt tokens per prefill chunk "
                         "(--chunk-prefill)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="max prefill tokens per step; runs "
                         "floor(budget/chunk_size) chunk lanes per step "
                         "(default: one lane).  The prefill-vs-decode "
                         "SLO knob: higher = faster admission TTFT, "
                         "more work per step")
    # HTTP/SSE serving (DESIGN.md §15)
    ap.add_argument("--serve", action="store_true",
                    help="start the HTTP/SSE streaming server "
                         "(serving/server.py) over a StreamEngine; "
                         "stop with POST /shutdown or Ctrl-C")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8763,
                    help="--serve port (0 = ephemeral)")
    ap.add_argument("--serve-smoke", action="store_true",
                    help="CI smoke: in-process SSE server, two "
                         "overlapping streaming requests with ordered "
                         "per-token events, a mid-stream reconnect "
                         "replay and a graceful shutdown")
    ap.add_argument("--prefix-smoke", action="store_true",
                    help="CI smoke: two requests sharing a --prefix-len "
                         "token prefix must share blocks and skip the "
                         "covered prefill")
    ap.add_argument("--prefix-len", type=int, default=128)
    ap.add_argument("--fault-smoke", action="store_true",
                    help="CI smoke: seeded fault-injection run "
                         "(serving.faults.FaultPlan) asserting zero leaked "
                         "blocks, zero replans and survivor token identity")
    ap.add_argument("--assert-no-replan", action="store_true",
                    help="fail if any TT execution plan is resolved during "
                         "the steady-state serving run (CI smoke for the "
                         "plan-compile-execute contract, DESIGN.md §10)")
    # durability (DESIGN.md §13)
    ap.add_argument("--compile-cache", default=None,
                    help="persistent XLA compilation cache dir (default "
                         "<checkout>/.compile_cache; "
                         "$JAX_COMPILATION_CACHE_DIR, when set, wins); a "
                         "restarted process reuses every compiled program")
    ap.add_argument("--assert-cache-hits", action="store_true",
                    help="fail if this run adds any entry to "
                         "--compile-cache (CI warm-start smoke: the "
                         "second run must compile nothing)")
    ap.add_argument("--first-token", action="store_true",
                    help="print a COLD_START line with process start -> "
                         "first token; run twice against one "
                         "--compile-cache dir for cold vs. warm")
    ap.add_argument("--durable", default=None,
                    help="journal + snapshot dir: submits/retires are "
                         "journaled, snapshots committed every "
                         "--snapshot-every steps; Ctrl-C preserves "
                         "in-flight work for --restore")
    ap.add_argument("--restore", action="store_true",
                    help="recover the scheduler from --durable (newest "
                         "clean snapshot + journal replay) and drain the "
                         "restored requests")
    ap.add_argument("--snapshot-every", type=int, default=32,
                    help="decode steps between snapshot generations "
                         "(--durable)")
    ap.add_argument("--durability-smoke", action="store_true",
                    help="CI drill: seeded kill -9 recovery (clean and "
                         "corrupted store), truncation/bit-flip fallback")
    args = ap.parse_args(argv)
    if args.slots is None:
        args.slots = args.batch
    if args.restore and not args.durable:
        ap.error("--restore requires --durable DIR")
    args.mesh_obj = None
    if args.mesh is not None:
        scheduler_mode = (args.arrival_rate is not None or args.restore
                          or args.fault_smoke or args.prefix_smoke
                          or args.durability_smoke or args.serve
                          or args.serve_smoke)
        if not scheduler_mode:
            ap.error("--mesh applies to scheduler modes only (use "
                     "--arrival-rate / --restore / the scheduler smokes); "
                     "the fixed-batch and --first-token paths run "
                     "single-device")
        from .mesh import make_serve_mesh
        args.mesh_obj = make_serve_mesh(args.mesh)
        print(f"serving over mesh {dict(args.mesh_obj.shape)} "
              f"({len(args.mesh_obj.devices.ravel())} devices)")

    cache_dir = enable_compile_cache(args.compile_cache)
    args.compile_cache = cache_dir
    n_cache0 = cache_entries(cache_dir)

    tt = None
    if args.tt:
        tt = TTConfig(enabled=True, families=tuple(args.tt.split(",")),
                      rank=args.tt_rank, backend=args.tt_backend,
                      autotune=args.tt_autotune, weights=args.tt_weights,
                      min_factor=2 if args.variant == "smoke" else 8)
    cfg = get_config(args.arch, args.variant, tt=tt)
    model = build(cfg, param_dtype=jnp.bfloat16
                  if args.variant == "full" else jnp.float32)
    params = model.init(jax.random.PRNGKey(args.seed))
    if args.tt and args.tt_weights == "int8":
        # offline checkpoint transform: int8 cores + per-core scales
        params = model.quantize_params(params)

    try:
        if args.prefix_smoke:
            out = prefix_smoke(model, params, args)
        elif args.fault_smoke:
            out = fault_smoke(model, params, args)
        elif args.durability_smoke:
            out = durability_smoke(model, params, args)
        elif args.first_token:
            out = first_token(model, params, args)
        elif args.serve_smoke:
            out = serve_smoke(model, params, args)
        elif args.serve:
            out = serve_mode(model, params, args)
        elif args.arrival_rate is not None or args.restore:
            if args.arrival_rate is None:
                args.arrival_rate = 1.0   # --restore drains, no arrivals
            out = simulate(model, params, args)
        else:
            out = fixed(model, params, args)
    except KeyboardInterrupt:
        # simulate() drains gracefully on its own; this is the safety net
        # for the other modes — exit 0 without a traceback
        print("\ninterrupted — exiting")
        return {"interrupted": True}
    n1 = cache_entries(cache_dir)
    print(f"compile cache {cache_dir}: {n_cache0} -> {n1} entries "
          f"({n1 - n_cache0} new compilations persisted)")
    if args.assert_cache_hits and (n1 != n_cache0 or n_cache0 == 0):
        raise AssertionError(
            f"warm start compiled {n1 - n_cache0} new programs "
            f"(cache had {n_cache0} entries) — the persistent "
            f"compilation cache must make a restart re-jit nothing")
    return out


if __name__ == "__main__":
    main()
