"""The control of the ``correct`` comparison: the plain reference put in
the program's place with its ranking and Saving rounded to the
configuration's ``control_precision``, judged by the same comparison as a
run. A sound comparison refuses it.

    python3 benchmarks/chip/chipbench/control.py --config graph500 \\
        --seeds 11 12 13

prints one JSON line per seed with the counts the control read.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent


def control_counts(cfg: dict, generate, seed: int) -> dict:
    """The comparison's counts for the control's output against the exact
    reference, on the configuration's graph for ``seed``."""
    from chipbench import correct, reference

    n, edges = generate(cfg, seed)
    exact = reference.summarize(n, edges, T=int(cfg["T"]), seed=seed)
    low = reference.summarize(n, edges, T=int(cfg["T"]), seed=seed,
                              precision=cfg["control_precision"])
    return correct.total(correct.compare(
        [(low["parent"], low["edges"], 0)], exact))


def main(argv=None) -> int:
    sys.path.insert(0, str(HERE.parent))
    from chipbench.bench import load_module, read_json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cfg = read_json(HERE.parent / "configs" / f"{args.config}.json")
    gen = load_module(HERE.parent / "generators" / f"{cfg['generator']}.py")
    for seed in args.seeds:
        t0 = time.perf_counter()
        found = control_counts(cfg, gen.generate, seed)
        print(json.dumps({"config": args.config, "seed": seed,
                          "precision": cfg["control_precision"],
                          "found": found,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
