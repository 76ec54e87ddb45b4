"""Benchmark harness: one module per paper table/figure + the roofline report.

  PYTHONPATH=src python -m benchmarks.run            # quick (default)
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale settings
  PYTHONPATH=src python -m benchmarks.run --only compactness,iterations
"""
from __future__ import annotations

import argparse
import time

from benchmarks import (compactness, composition, decompression, height,
                        iterations, merge_throughput, pipeline_breakdown,
                        pruning_bench, query_serving, roofline_report,
                        scalability, speed)
from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "compactness": compactness.run,     # Fig 5a / Fig 1a
    "speed": speed.run,                 # Fig 5b
    "merge": merge_throughput.run,      # batched-engine speedup (BENCH_merge)
    "pipeline": pipeline_breakdown.run, # stage-level IR speedups (BENCH_pipeline)
    "serving": query_serving.run,       # batched query qps (BENCH_serving_queries)
    "scalability": scalability.run,     # Fig 1b
    "partitioned": scalability.run_partitioned,  # engine partition sweep (BENCH_partitioned)
    "resident": scalability.run_resident,  # resident merge rounds (BENCH_resident)
    "iterations": iterations.run,       # Table III
    "pruning": pruning_bench.run,       # Table IV
    "height": height.run,               # Table V
    "composition": composition.run,     # Fig 6
    "decompression": decompression.run, # §VIII-B
    "roofline": roofline_report.run,    # framework §Roofline
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None, help="comma-separated suite names")
    args = ap.parse_args(argv)
    enable_compile_cache()
    only = args.only.split(",") if args.only else list(SUITES)
    t0 = time.perf_counter()
    for name in only:
        t1 = time.perf_counter()
        SUITES[name](quick=not args.full)
        print(f"   [{name} done in {time.perf_counter()-t1:.1f}s]")
    print(f"\nAll benchmarks done in {time.perf_counter()-t0:.1f}s")


if __name__ == "__main__":
    main()
