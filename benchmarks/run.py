"""Benchmark harness entry point — one benchmark per paper table/figure:

  fig1_drift      paper Fig. 1  incremental-KPCA reconstruction drift
  fig2_nystrom    paper Fig. 2  incremental-Nyström approximation error
  flops_table     paper §3      8m³-vs-20m³ efficiency claim
  timing          (supporting)  measured incremental-vs-batch scaling
  update_scaling  (supporting)  per-update cost vs active m: fixed-capacity
                                vs bucketed dispatch (BENCH_update_scaling.json)
  multitenant     (supporting)  vmapped multi-tenant ingest vs a Python loop
                                over B streams (BENCH_multitenant.json)
  roofline        (supporting)  per-kernel achieved-vs-peak bandwidth and
                                the fused-vs-unfused gates (BENCH_roofline.json)

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
"""
from __future__ import annotations

import argparse
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer repetitions / smaller streams")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import bench_multitenant, bench_update_scaling, \
        fig1_drift, fig2_nystrom, flops_table, roofline, timing

    benches = {
        "flops_table": lambda: flops_table.main(),
        "fig1_drift": lambda: fig1_drift.main(
            runs=2 if args.quick else 5,
            n_stream=120 if args.quick else 280),
        "fig2_nystrom": lambda: fig2_nystrom.main(
            runs=1 if args.quick else 3, n=400 if args.quick else 1000),
        "timing": lambda: timing.main(),
        "update_scaling": lambda: bench_update_scaling.main(
            quick=args.quick),
        "multitenant": lambda: bench_multitenant.main(
            rounds=10 if args.quick else 20),
        "roofline": lambda: roofline.main(quick=args.quick),
    }
    failures = []
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        print(f"\n=== {name} {'=' * (60 - len(name))}")
        t0 = time.time()
        try:
            fn()
            print(f"=== {name} done in {time.time() - t0:.1f}s")
        except Exception as e:      # noqa: BLE001
            failures.append((name, repr(e)))
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    main()
