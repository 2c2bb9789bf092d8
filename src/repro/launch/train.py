"""Production training launcher.

On a real cluster every host runs this same script (jax.distributed
initializes from env); on this CPU container it drives the identical code
path on a (1, 1) mesh — the point of expressing everything through GSPMD
shardings is that the program is mesh-size-agnostic.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b \
      --variant smoke --steps 100 --batch 8 --seq 128 \
      --tt ffn --tt-rank 16 --ckpt-dir /tmp/run1

Fault tolerance: atomic checkpoints every --save-every steps (+ on
SIGTERM), restart resumes bit-identically (tests/test_system.py).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import build, get_config
from repro.configs.base import TTConfig
from repro.data.pipeline import DataIterator, DataState
from repro.distributed import sharding as shd
from repro.training.fault import CheckpointManager, restore_or_init
from repro.training.optimizer import OptConfig, adamw_init
from repro.training.train_loop import TrainConfig, make_train_step


def make_mesh_from_devices():
    """Largest (data, model) mesh the available devices support."""
    n = len(jax.devices())
    model = 1
    for m in (16, 8, 4, 2, 1):
        if n % m == 0 and m <= n:
            model = m
            break
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--micro-batches", type=int, default=1)
    ap.add_argument("--tt", default=None,
                    help="comma list of families to TT-factorize (e.g. "
                         "'ffn' or 'ffn,attn'); omit for dense")
    ap.add_argument("--tt-rank", type=int, default=16)
    ap.add_argument("--tt-backend", default="xla")
    ap.add_argument("--tt-autotune", default="cached",
                    choices=["off", "cached", "measure"],
                    help="block-plan autotuner mode for the Pallas backends")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    tt = None
    if args.tt:
        tt = TTConfig(enabled=True, families=tuple(args.tt.split(",")),
                      rank=args.tt_rank, backend=args.tt_backend,
                      autotune=args.tt_autotune,
                      min_factor=2 if args.variant == "smoke" else 8)
    cfg = get_config(args.arch, args.variant, tt=tt)
    model = build(cfg)

    mesh = make_mesh_from_devices()
    rules = dict(shd.ACT_RULES_TRAIN)
    shd.set_ctx(shd.ShardCtx(mesh, rules, ("data",)))

    tcfg = TrainConfig(
        opt=OptConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                      total_steps=args.steps),
        micro_batches=args.micro_batches,
        compute_dtype=jnp.bfloat16 if args.variant == "full"
        else jnp.float32,
        grad_compression=args.grad_compression,
    )
    step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0,))

    def init_fn():
        params = model.init(jax.random.PRNGKey(args.seed))
        state = {"params": params, "opt": adamw_init(params)}
        if tcfg.grad_compression:
            from repro.training.compression import ef_init
            state["ef"] = ef_init(params)
        return state

    start_step, data_state = 0, {}
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, save_every=args.save_every)
        mgr.install_preemption_handler()
        state, start_step, data_state = restore_or_init(
            mgr, init_fn, init_fn())
    else:
        state = init_fn()

    n_params = model.num_params()
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M mesh={mesh.shape} "
          f"tt={'on' if cfg.tt.enabled else 'off'} start={start_step}")

    it = DataIterator(cfg, args.batch, args.seq,
                      state=DataState.from_dict(data_state or {}))
    losses, t0 = [], time.time()
    for step in range(start_step + 1, args.steps + 1):
        state, metrics = step_fn(state, next(it))
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps:
            dt = (time.time() - t0) / max(len(losses), 1)
            tok_s = args.batch * args.seq / dt
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"| {dt*1e3:.0f} ms/step {tok_s:.0f} tok/s "
                  f"lr {float(metrics['lr']):.2e}")
        if mgr and mgr.should_save(step):
            mgr.save(state, step, data_state=it.state.as_dict())
        if mgr and mgr.preempted:
            print(f"preempted at step {step}: checkpoint saved, exiting")
            break
    if mgr:
        mgr.save(state, args.steps, data_state=it.state.as_dict())
    shd.set_ctx(None)
    return {"final_loss": losses[-1] if losses else None,
            "first_loss": losses[0] if losses else None,
            "steps_run": len(losses),
            "params": n_params,
            # for in-process consumers (examples): the trained parameter
            # tree and the built model, so a serving step can run on the
            # result without a checkpoint round-trip
            "model": model,
            "trained_params": state["params"]}


if __name__ == "__main__":
    out = main()
    print(f"done: first_loss={out['first_loss']:.4f} "
          f"final_loss={out['final_loss']:.4f}")
