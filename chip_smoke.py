#!/usr/bin/env python3
"""On-chip smoke test of the serving path: full-width deepseek-7b with
TT-factorized FFNs on one TPU v5e chip, through the normal entry point
(``repro.launch.serve.main``) with the TT chain in Pallas kernels.

This is a smoke, not a benchmark.  It shows that the main path compiles
and runs on the chip and that what comes out is right; the times it
prints are not speed results.  Weights are random, drawn from a seed.

    python3 chip_smoke.py             # one chip: phases (a)-(d)
    python3 chip_smoke.py --chips 4   # four chips: mesh serving only

Phases, all in this one process (a chip belongs to one process):

(a) The device: platform, kind and count on an early line.  Without a TPU
    the script exits non-zero and never falls back to the CPU.
(b) The kernels: for every TT chain of the model's ``PlanBook``, the
    Pallas plan against the XLA chain computed in fp32 at "highest"
    precision, fp (bf16) and int8-resident cores, at real widths.
(c) Serving fp-resident cores: paged KV pool, chunked prefill, 8 slots,
    16 Poisson requests of 512 prompt tokens and 64 new tokens.  Every
    request finishes with 64 tokens, no TT plan is resolved while serving,
    the logits are finite, and the compiled decode step holds the Pallas
    kernels (``tpu_custom_call``), not an XLA stand-in.
(d) The same with int8-resident cores (``--tt-weights int8``).

``--chips 4`` runs only the mesh path and what it is compared with: the
same model and workload served over ``make_serve_mesh(4)`` and with no
mesh.  Greedy tokens must agree; where a request's tokens diverge, its
first-step logits must agree within ``MESH_LOGIT_TOL``.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failure raises, so the script exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH, VARIANT = "deepseek-7b", "full"
SLOTS, PROMPT_LEN, NEW_TOKENS, REQUESTS = 8, 512, 64, 16
SEED = 0


def serve_args() -> list[str]:
    return ["--arch", ARCH, "--variant", VARIANT, "--tt", "ffn",
            "--tt-backend", "auto", "--paged", "--chunk-prefill",
            "--slots", str(SLOTS), "--prompt-len", str(PROMPT_LEN),
            "--steps", str(NEW_TOKENS), "--max-requests", str(REQUESTS),
            "--arrival-rate", "8", "--seed", str(SEED),
            "--assert-no-replan"]


# Phase (b): relative L2 error of a Pallas TT layer against the fp32 XLA
# chain.  Inputs and cores are bf16 in both; the kernel returns bf16, a
# rounding of 2^-9 per element (about 2e-3 in L2), and its fp32 MXU
# products may round operands to bf16 (about 4e-3 per step, two steps).
# 1e-2 leaves room for both and is far below what a wrong index or
# relayout produces (order 1).
KERNEL_TOL = 1e-2
# --chips 4: relative L2 error of first-step logits, mesh against no mesh.
# Activations are bf16: the model axis splits each head-sharded
# contraction into four partial sums rounded to bf16 before the
# all-reduce, and 30 layers compound that rounding.
MESH_LOGIT_TOL = 5e-2


def rel_err(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def phase_device(chips: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"(a) device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        raise SystemExit("chip_smoke needs a TPU; JAX found "
                         f"{dev['platform']!r} and this smoke never falls "
                         "back to it")
    if dev["count"] < chips:
        raise SystemExit(f"--chips {chips} needs {chips} devices, JAX "
                         f"found {dev['count']}")
    return dev


def phase_kernels() -> None:
    from repro.configs import build, get_config
    from repro.configs.base import TTConfig
    from repro.core.quant import dequantize_cores, quantize_cores
    from repro.core.tt import TTPlan, tt_init
    from repro.kernels.ops import tt_forward

    tt = TTConfig(enabled=True, families=("ffn",), rank=16, backend="auto",
                  min_factor=8)
    model = build(get_config(ARCH, VARIANT, tt=tt),
                  param_dtype=jnp.bfloat16)
    book = model.plan_book
    chains = sorted({key[:3] for key in book.plans})
    key = jax.random.PRNGKey(SEED)
    f32 = jnp.float32
    for ns, ms, ranks in chains:
        key, kc, kx = jax.random.split(key, 3)
        cores = [c.astype(jnp.bfloat16)
                 for c in tt_init(kc, TTPlan(ms, ns, ranks))]
        qcores, scales = quantize_cores(cores)
        for B in (SLOTS, PROMPT_LEN):
            x = jax.random.normal(kx, (B, int(np.prod(ns))), f32).astype(
                jnp.bfloat16)
            with jax.default_matmul_precision("highest"):
                ref_fp = tt_forward([c.astype(f32) for c in cores],
                                    x.astype(f32), backend="xla")
                ref_q = tt_forward(dequantize_cores(qcores, scales, f32),
                                   x.astype(f32), backend="xla")
            for weights, ref in (("fp", ref_fp), ("int8", ref_q)):
                plan = book.plan_for(ns, ms, ranks, weights=weights,
                                     weight_itemsize=2)
                if not plan.backend.startswith("pallas"):
                    raise AssertionError(f"{plan.describe()} is no Pallas "
                                         "plan")
                got = (tt_forward(qcores, x, scales=scales, plan=plan)
                       if weights == "int8" else
                       tt_forward(cores, x, plan=plan))
                got = np.asarray(got.astype(f32))
                err = rel_err(got, ref)
                print(f"(b) {plan.describe()} B={B}: rel L2 err {err:.2e} "
                      f"(tol {KERNEL_TOL:g})", flush=True)
                if got.shape != ref.shape or not np.isfinite(got).all():
                    raise AssertionError(f"bad output {got.shape}")
                if err > KERNEL_TOL:
                    raise AssertionError(
                        f"Pallas and XLA disagree: {err:.3e} > {KERNEL_TOL}")


def decode_kernel_calls(sched) -> int:
    """Pallas kernels in the compiled masked decode step the scheduler
    ran (its persistent-cache entry makes this compile a lookup)."""
    toks = jnp.zeros((sched.num_slots, 1), jnp.int32)
    active = jnp.zeros((sched.num_slots,), bool)
    ctx = (jax.set_mesh(sched.mesh) if sched.mesh is not None
           else contextlib.nullcontext())
    with ctx:
        hlo = sched.model.jitted_decode_step_masked(sched.mesh).lower(
            sched.params, sched.cache, toks, active).compile().as_text()
    return hlo.count('custom_call_target="tpu_custom_call"')


def serve(label: str, extra: list[str]) -> dict:
    """Serve the workload through ``serve.main`` and check what came out."""
    from repro.launch import serve as serve_cli

    t0 = time.perf_counter()
    out = serve_cli.main(serve_args() + extra)
    fin = sorted(out["finished"], key=lambda f: f.uid)
    if len(fin) != REQUESTS:
        raise AssertionError(f"{len(fin)} of {REQUESTS} requests finished")
    for f in fin:
        if len(f.tokens) != NEW_TOKENS or f.finish_reason != "length":
            raise AssertionError(
                f"request {f.uid}: {len(f.tokens)} tokens, "
                f"{f.finish_reason!r}")
    if out["replans"] != 0:
        raise AssertionError(f"{out['replans']} plan resolutions")
    # a row of logits with a NaN or +inf has no finite log-softmax, so
    # finite logprobs of every emitted token mean finite logits
    lp = np.concatenate([f.logprobs for f in fin])
    if not np.isfinite(lp).all():
        raise AssertionError("non-finite logits while serving")
    calls = decode_kernel_calls(out["scheduler"])
    if calls < 1:
        raise AssertionError("the compiled decode step holds no Pallas "
                             "kernel")
    print(f"{label}: {len(fin)} requests x {NEW_TOKENS} tokens, 0 replans, "
          f"logprobs finite (min {lp.min():.2f}), {calls} tpu_custom_call "
          f"in the decode step; smoke wall {time.perf_counter() - t0:.1f}s "
          f"incl. compile (not a speed result)", flush=True)
    out["tokens"] = {f.uid: f.tokens.tolist() for f in fin}
    return out


def first_step_logits(sched) -> np.ndarray:
    """Prefill logits of every request's prompt (the ones its first token
    is picked from), through the scheduler's params and mesh."""
    from repro.configs.shapes import concrete_batch

    fn = sched.model.jitted_prefill()
    ctx = (jax.set_mesh(sched.mesh) if sched.mesh is not None
           else contextlib.nullcontext())
    rows = []
    with ctx:
        for uid in range(REQUESTS):
            toks = concrete_batch(sched.model.cfg, 1, PROMPT_LEN,
                                  seed=SEED + uid)["tokens"]
            logits, _ = fn(sched.params, {"tokens": toks})
            rows.append(np.asarray(logits, np.float32).reshape(-1))
    return np.stack(rows)


def phase_mesh() -> None:
    mesh = serve("(mesh) served over make_serve_mesh(4)", ["--mesh", "4"])
    mesh_logits = first_step_logits(mesh["scheduler"])
    mesh_tokens = mesh["tokens"]
    del mesh                      # free the mesh run before the next one
    gc.collect()
    single = serve("(mesh) served with no mesh", [])
    single_logits = first_step_logits(single["scheduler"])
    errs = [rel_err(mesh_logits[u], single_logits[u])
            for u in range(REQUESTS)]
    diverged = [u for u in range(REQUESTS)
                if mesh_tokens[u] != single["tokens"][u]]
    print(f"(mesh) greedy tokens identical for "
          f"{REQUESTS - len(diverged)}/{REQUESTS} requests; first-step "
          f"logits rel L2 err max {max(errs):.2e} "
          f"(tol {MESH_LOGIT_TOL:g} where tokens diverge)", flush=True)
    for u in diverged:
        first = next(i for i, (a, b) in enumerate(
            zip(mesh_tokens[u], single["tokens"][u])) if a != b)
        print(f"(mesh) request {u}: tokens diverge at step {first}, "
              f"first-step logits rel L2 err {errs[u]:.2e}", flush=True)
        if errs[u] > MESH_LOGIT_TOL:
            raise AssertionError(
                f"request {u}: mesh and single-chip logits disagree "
                f"({errs[u]:.3e} > {MESH_LOGIT_TOL})")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the mesh-serving phase and its "
                         "single-chip comparison")
    args = ap.parse_args(argv)
    print("chip smoke (not a benchmark): deepseek-7b full width, TT FFNs "
          "in Pallas, random weights", flush=True)
    dev = phase_device(args.chips)

    from repro.launch.cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        phase_mesh()
    else:
        phase_kernels()
        serve("(c) fp-resident cores", [])
        gc.collect()              # (c)'s params and pool leave the chip
        serve("(d) int8-resident cores", ["--tt-weights", "int8"])
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
