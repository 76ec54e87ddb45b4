"""Chaos smoke: kill the summarizer at every stage boundary and prove the
plan-log checkpoint resumes bit-identically (DESIGN.md §11).

Default mode injects an `InjectedFault` at each of the five engine stage
boundaries (``engine.shingle``/``group``/``pack``/``merge_round``/
``exchange``) mid-run, then resumes from the surviving checkpoint and
asserts the summary equals an uninterrupted run array-for-array — the CI
teeth behind the crash-safety claim. ``--kernel-fault`` instead injects a
Pallas dispatch fault into a resident-backend run and asserts the engine
finishes on the jnp twin with a lossless, numpy-identical summary and a
non-zero degradation count (pair with ``REPRO_FORCE_PALLAS=1`` so the
kernel path is actually live on CPU).

CI usage:
  PYTHONPATH=src python -m repro.launch.chaos
  REPRO_FORCE_PALLAS=1 PYTHONPATH=src python -m repro.launch.chaos --kernel-fault
"""
from __future__ import annotations

import argparse
import shutil
import tempfile

import numpy as np

from repro import faults
from repro.core.engine import STAGE_ORDER, SummarizerEngine
from repro.graphs import generators
from repro.launch.compile_cache import enable_compile_cache


def _engine(backend: str = "numpy", partitions: int = 1,
            T: int = 5) -> SummarizerEngine:
    return SummarizerEngine(partitions=partitions, backend=backend, T=T,
                            seed=3)


def run_stage_kills(T: int = 5, kill_at: int = 3) -> int:
    """Kill at every stage boundary of iteration ``kill_at``; resume each
    time and demand bit-identity with the uninterrupted run."""
    g = generators.caveman(14, 6, 0.05, seed=13)
    want = _engine(T=T).run(g)
    assert want.validate_lossless(g)
    for stage in STAGE_ORDER:
        ckpt = tempfile.mkdtemp(prefix=f"slugger-chaos-{stage}-")
        try:
            try:
                with faults.inject(f"engine.{stage}", iteration=kill_at):
                    _engine(T=T).run(g, checkpoint_dir=ckpt)
                raise AssertionError(f"engine.{stage} fault never fired")
            except faults.InjectedFault:
                pass
            eng = _engine(T=T)
            got = eng.run(g, checkpoint_dir=ckpt, resume=True)
            resumed = eng.stats.get("resumed_from")
            # the commit lands AFTER iteration kill_at's stages, so every
            # kill inside iteration kill_at resumes from kill_at - 1
            assert resumed == kill_at - 1, (stage, resumed)
            assert np.array_equal(got.parent, want.parent), stage
            assert np.array_equal(got.edges, want.edges), stage
            assert got.validate_lossless(g), stage
            print(f"[chaos] kill @ engine.{stage} (iter {kill_at}): resumed "
                  f"from {resumed}, bit-identical")
        finally:
            shutil.rmtree(ckpt, ignore_errors=True)
    print(f"[chaos] OK: {len(STAGE_ORDER)} stage-boundary kills, "
          f"{len(STAGE_ORDER)} bit-identical resumes")
    return 0


def run_kernel_fault(T: int = 3) -> int:
    """Inject a Pallas dispatch fault into a resident run: the engine must
    retry on the jnp reference twin and finish losslessly, numpy-identical,
    with the degradation counted."""
    g = generators.caveman(40, 5, 0.05, seed=0)
    want = _engine(T=T).run(g)
    eng = _engine(backend="resident", T=T)
    # kernel sites carry no engine-iteration context (the check sits in the
    # dispatch wrapper) — target the Nth dispatch instead
    with faults.inject("kernel.bitset_fold.round", hit=2):
        got = eng.run(g)
    degr = eng.stats["degradations"]
    assert degr > 0, "kernel fault injected but no degradation recorded"
    assert np.array_equal(got.parent, want.parent)
    assert np.array_equal(got.edges, want.edges)
    assert got.validate_lossless(g)
    print(f"[chaos] OK: kernel dispatch fault degraded to the jnp twin "
          f"({degr} degradation(s)), summary lossless and numpy-identical")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernel-fault", action="store_true",
                    help="resident-backend Pallas dispatch fault → jnp-twin "
                         "fallback (pair with REPRO_FORCE_PALLAS=1)")
    ap.add_argument("--iters", type=int, default=5,
                    help="engine iterations T for the stage-kill mode")
    ap.add_argument("--kill-at", type=int, default=3,
                    help="iteration the stage-boundary faults fire in")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.kernel_fault:
        return run_kernel_fault()
    return run_stage_kills(T=args.iters, kill_at=args.kill_at)


if __name__ == "__main__":
    raise SystemExit(main())
