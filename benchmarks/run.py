"""Benchmark harness: one module per paper table/figure.

  python -m benchmarks.run            # everything
  python -m benchmarks.run --quick    # reduced grids (CI)
  python -m benchmarks.run --only alignment

Tables/figures covered:
  Fig 2b      bench_flops_vs_time   FLOPs ≠ runtime (motivates stage 2)
  Tables 1–2  bench_ds_reduction    DS size per pruning stage
  Figs 5–8    bench_alignment       ratio_FLOPs / ratio_Memory
  Fig 11      bench_fc_fraction     FC share of inference time
  Figs 12–14  bench_einsum_kernels  first/middle/final kernels, CB0–CB7
  Fig 15      bench_end_to_end      dense vs TT FC layers (§6.4 picks)
  Fig 16      bench_breakdown       progressive optimization stages
  §Roofline   repro.analysis.roofline --table  (reads results/dryrun)
  DESIGN §8   bench_quant           int8-resident kernels: weights x
                                    backend x depth (+ fused-under-int8
                                    showcase) -> results/BENCH_quant.json
  DESIGN §12  bench_dse_quality     analytic-proxy vs quality-gated DSE
                                    fronts per config family ->
                                    results/BENCH_dse.json
"""
from __future__ import annotations

import argparse
import sys
import time


BENCHES = ["ds_cloud", "ds_reduction", "alignment", "einsum_kernels",
           "end_to_end", "breakdown", "fc_fraction", "flops_vs_time",
           "serve_tt", "quant", "dse_quality"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, choices=BENCHES)
    args = ap.parse_args()

    # persistent compile cache (launch.cache): benchmark reruns skip
    # every compile a previous invocation already paid for
    from repro.launch.cache import enable_compile_cache
    print(f"# persistent compile cache: {enable_compile_cache()}")

    names = [args.only] if args.only else BENCHES
    t_all = time.time()
    failures = []
    for name in names:
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        t0 = time.time()
        try:
            mod.run(quick=args.quick)
        except Exception as e:           # keep the harness going
            failures.append((name, repr(e)))
            print(f"!! bench_{name} FAILED: {e!r}")
        print(f"# bench_{name}: {time.time() - t0:.1f}s")
    print(f"\n# total: {time.time() - t_all:.1f}s")
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
