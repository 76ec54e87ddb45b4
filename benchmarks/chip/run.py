"""Run one cell of BENCHMARK.json once, on the chip this process finds.

    python3 benchmarks/chip/run.py --workload graph500.summarize \\
        --seed 12345 --seconds 10 --trace 0

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device`` and, last, ``checks``: each
number the correctness comparison read, beside its limit. The same checks
are the last lines of stderr. Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.

JAX's persistent compilation cache lives at the program's fixed path inside
the checkout (``.jax_cache``), or where ``JAX_COMPILATION_CACHE_DIR`` says.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T_PROCESS:.1f}s] {msg}",
          file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        log("FAIL: --seed must be a whole number >= 0")
        return 2
    sys.path.insert(0, str(BENCH_DIR))
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import bench

    cell = bench.resolve(ROOT, args.workload, args.seed, args.seconds,
                         bool(args.trace), T_PROCESS, log=log)
    try:
        device = bench.device_info(int(cell.workload["chips"]))
    except RuntimeError as e:
        log(f"FAIL: {e}")
        return 1
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    # every program goes to the cache, so that only a cell's first run in
    # a checkout compiles; and no size cap: with one, JAX guards every cache
    # read with a file lock held for 10 s at most, and the engine's
    # concurrent chunk compiles then time out on it and compile again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    log(f"device: {device}; jax {jax.__version__}")
    result = bench.run_cell(cell, device)
    print(json.dumps(result), flush=True)
    bench.print_checks(result["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
