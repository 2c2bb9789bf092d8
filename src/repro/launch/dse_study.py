"""DSE study launcher: data-aware trial evaluation over one projection.

Runs the ``core.study`` engine (DESIGN.md §12) for a model's projection
shape: enumerate the funnel's survivors, evaluate each trial end-to-end
(activation-aware score, perplexity delta vs the dense reference through
a frozen-plan TT twin, optional serving tok/s), persist every outcome to
a schema-versioned JSON state file, and print the measured ranking plus
the gated pareto front.  Interrupt it any time — rerunning the same
command resumes from the state file and re-derives identical results.

  PYTHONPATH=src python -m repro.launch.dse_study --arch deepseek-7b \
      --variant smoke --max-trials 8 --measure-tok-s

Smoke mode (CI): a 2-trial study on the smoke config's FFN shape, run
once straight through and once interrupted-after-trial-0 + resumed from
the persisted state — asserts the two produce bit-identical rankings and
metrics (the resume-determinism contract), and that every trial measured
zero plan re-resolutions.

  PYTHONPATH=src python -m repro.launch.dse_study --smoke
"""
from __future__ import annotations

import argparse
import json
import os

from repro.configs import get_config
from repro.core.dse import DSEConfig, pareto_front
from repro.core.study import (EvaluatorConfig, Study, make_model_evaluator)


def _dse_config(args) -> DSEConfig:
    return DSEConfig(vl=args.vl, rank_step=args.rank_step,
                     rank_cap=args.rank_cap, max_d=args.max_d,
                     min_factor=args.min_factor,
                     weight_dtypes=tuple(args.dtypes.split(",")))


def _trial_rows(study: Study) -> list[dict]:
    return [{"tid": t.tid, "status": t.status,
             "plan": t.solution.plan.describe(),
             "weight_dtype": t.solution.weight_dtype,
             "flops": t.solution.flops, "bytes": t.solution.bytes,
             "err_proxy": t.solution.err_proxy, **t.metrics}
            for t in study.trials]


def run_study(args) -> dict:
    cfg = get_config(args.arch, args.variant)
    M = args.M if args.M else cfg.d_ff
    N = args.N if args.N else cfg.d_model
    dse = _dse_config(args)
    state = args.state or os.path.join(
        "results", f"dse_study_{args.arch}_{M}x{N}.json")
    ecfg = EvaluatorConfig(n_calib=args.calib_batches,
                           n_eval=args.eval_batches,
                           batch=args.calib_batch, seq=args.calib_seq,
                           measure_tok_s=args.measure_tok_s,
                           serve_steps=args.serve_steps,
                           finetune_steps=args.finetune_steps)
    study = Study.open(state, M, N, dse, seed=args.seed,
                       max_trials=args.max_trials)
    print(f"study {state}: [{M}x{N}] {len(study.trials)} trials, "
          f"{len(study.pending())} pending")
    evaluate = make_model_evaluator(cfg, ecfg, seed=args.seed)
    study.run(evaluate, batch_size=args.batch_size, log=print)

    ranked = study.ranking()
    print(f"\n  {'tid':>3} {'plan':<46} {'dtype':<5} {'act_err':>8} "
          f"{'ppl_delta':>9} {'tok/s':>8}")
    for t in ranked:
        print(f"  {t.tid:>3} {t.solution.plan.describe():<46} "
              f"{t.solution.weight_dtype:<5} "
              f"{t.metrics.get('act_err', float('nan')):>8.4f} "
              f"{t.metrics.get('ppl_delta', float('nan')):>9.4f} "
              f"{t.metrics.get('tok_s', float('nan')):>8.1f}")
    res = study.result()
    axes = ("flops", "bytes", "ppl_delta")
    front = pareto_front(res.solutions, axes=axes) if res.solutions else []
    print(f"\nmeasured front over {axes}:")
    for s in front:
        print(f"  {s.plan.describe()} {s.weight_dtype} "
              f"ppl_delta={s.ppl_delta:+.4f}")
    return {"state": state, "trials": _trial_rows(study),
            "front": [s.plan.describe() for s in front]}


def run_smoke(args) -> dict:
    """CI resume-determinism assertion (ISSUE 7 acceptance criterion)."""
    cfg = get_config(args.arch, "smoke")
    M, N = cfg.d_ff, cfg.d_model
    dse = DSEConfig(vl=4, rank_step=4, rank_cap=8, max_d=3, min_factor=2,
                    weight_dtypes=("fp32", "int8"))
    ecfg = EvaluatorConfig(n_calib=1, n_eval=1, batch=2, seq=16,
                           measure_tok_s=False)
    evaluate = make_model_evaluator(cfg, ecfg, seed=args.seed)
    os.makedirs("results", exist_ok=True)
    p_ref = os.path.join("results", "dse_study_smoke_ref.json")
    p_int = os.path.join("results", "dse_study_smoke_resume.json")
    for p in (p_ref, p_int):
        if os.path.exists(p):
            os.unlink(p)

    # uninterrupted reference run
    ref = Study.create(p_ref, M, N, dse, seed=args.seed, max_trials=2)
    ref.run(evaluate, batch_size=2)

    # interrupted run: evaluate trial 0, drop the in-memory object …
    interrupted = Study.create(p_int, M, N, dse, seed=args.seed,
                               max_trials=2)
    interrupted.run(evaluate, batch_size=1, max_trials=1)
    del interrupted
    # … resume purely from the persisted state and finish
    resumed = Study.load(p_int, dse)
    already = len(resumed.completed())
    if already != 1:
        raise AssertionError(f"resume should see exactly 1 completed "
                             f"trial, saw {already}")
    resumed.run(evaluate, batch_size=1)

    def record(study: Study) -> list[tuple]:
        return [(t.tid, t.status, json.dumps(t.metrics, sort_keys=True))
                for t in study.trials]

    if record(ref) != record(resumed):
        raise AssertionError(
            "resume is not deterministic:\n"
            f"  reference: {record(ref)}\n  resumed:   {record(resumed)}")
    ranks_equal = ([t.tid for t in ref.ranking()]
                   == [t.tid for t in resumed.ranking()])
    if not ranks_equal:
        raise AssertionError("resumed ranking differs from reference")
    for t in ref.completed():
        if t.metrics.get("plan_resolutions") != 0:
            raise AssertionError(
                f"trial {t.tid} measured {t.metrics['plan_resolutions']} "
                f"plan re-resolutions (must be 0)")
    print(f"dse-study smoke OK: {len(ref.trials)} trials, "
          f"interrupted-after-1 resume bit-identical, "
          f"0 plan re-resolutions, best tid={ref.best().tid}")
    return {"smoke": "ok", "trials": _trial_rows(ref)}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--variant", default="smoke",
                    choices=["smoke", "full"])
    ap.add_argument("--M", type=int, default=0,
                    help="projection out-dim (default: the arch's d_ff)")
    ap.add_argument("--N", type=int, default=0,
                    help="projection in-dim (default: the arch's d_model)")
    ap.add_argument("--state", default=None,
                    help="study state JSON (default: results/"
                         "dse_study_<arch>_<M>x<N>.json); resumed if "
                         "present")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-trials", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=2,
                    help="trials evaluated in parallel per checkpoint")
    # funnel knobs
    ap.add_argument("--vl", type=int, default=4)
    ap.add_argument("--rank-step", type=int, default=4)
    ap.add_argument("--rank-cap", type=int, default=16)
    ap.add_argument("--max-d", type=int, default=3)
    ap.add_argument("--min-factor", type=int, default=2)
    ap.add_argument("--dtypes", default="fp32,int8")
    # evaluator knobs
    ap.add_argument("--calib-batches", type=int, default=2)
    ap.add_argument("--eval-batches", type=int, default=2)
    ap.add_argument("--calib-batch", type=int, default=2)
    ap.add_argument("--calib-seq", type=int, default=32)
    ap.add_argument("--measure-tok-s", action="store_true",
                    help="measure scheduler decode tok/s per trial")
    ap.add_argument("--serve-steps", type=int, default=16)
    ap.add_argument("--finetune-steps", type=int, default=0,
                    help=">0: rank-adaptive TT-core finetune before the "
                         "perplexity measurement (training/finetune.py)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: 2 trials, interrupted + resumed, "
                         "bit-determinism asserted")
    ap.add_argument("--compile-cache", default=None,
                    help="persistent XLA compilation cache dir (default "
                         "<checkout>/.compile_cache; "
                         "$JAX_COMPILATION_CACHE_DIR, when set, wins): a "
                         "resumed study re-jits "
                         "none of the trial programs a previous process "
                         "already compiled")
    args = ap.parse_args(argv)
    from .cache import enable_compile_cache
    enable_compile_cache(args.compile_cache)
    if args.smoke:
        return run_smoke(args)
    return run_study(args)


if __name__ == "__main__":
    main()
