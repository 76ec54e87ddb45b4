"""CPU-side contract of the chip bring-up: `chip_smoke.py` refuses to report
without a TPU, the compile-cache helper's path rules, and the resident
path's no-hidden-fallback policy (a declined bank is recorded; a kernel the
compiler refuses fails the run instead of finishing on the jnp twin)."""
from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import faults
from repro.core.engine import SummarizerEngine
from repro.graphs import generators as GG
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
G = GG.caveman(14, 6, 0.05, seed=13)


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_fails_without_tpu(capsys):
    assert jax.default_backend() != "tpu"
    assert _load_chip_smoke().main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out


def test_chip_smoke_alone_fails(tmp_path):
    """Copied out of the repository, the script cannot find the program."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX


def test_compile_cache_fixed_repo_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert first == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_declined_bank_is_recorded(monkeypatch):
    from repro.core import resident

    def decline(*_a, **_k):
        raise OverflowError("forced: bank declines the graph")

    monkeypatch.setattr(resident, "ResidentAdjacencyBank", decline)
    want = SummarizerEngine(backend="numpy", T=3, seed=3).run(G)
    eng = SummarizerEngine(backend="resident", T=3, seed=3)
    mark = faults.DEGRADATIONS.count()
    got = eng.run(G)
    assert eng.stats["degradations"] >= 1
    sites = [e["site"] for e in faults.DEGRADATIONS.events_since(mark)]
    assert "resident.bank.init" in sites
    assert eng._run_ctx is not None and eng._run_ctx.bank is None
    assert np.array_equal(got.parent, want.parent)
    assert np.array_equal(got.edges, want.edges)


@pytest.mark.parametrize("kernel", ["jaccard_topj_kernel",
                                    "bitset_fold_kernel"])
def test_kernel_refusal_fails_the_run(monkeypatch, kernel):
    """A kernel that does not lower is a bring-up fault: the run raises, and
    nothing is retried on the jnp twin or recorded as a degradation."""
    from repro.kernels.bitset_fold import ops

    def refused(*_a, **_k):
        raise NotImplementedError(f"{kernel}: refused by the compiler")

    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    monkeypatch.setattr(ops, kernel, refused)
    for cache in (ops._ROUND_CACHE, ops._FOLDC_CACHE):
        monkeypatch.setattr(cache, "_d", type(cache._d)())
    mark = faults.DEGRADATIONS.count()
    with pytest.raises(NotImplementedError, match="refused by the compiler"):
        SummarizerEngine(backend="resident", T=3, seed=3).run(G)
    assert faults.DEGRADATIONS.count() == mark


def test_compile_clock_counts_overlapping_compiles_once():
    """Compiles on concurrent threads overlap: set-up is their union."""
    clock_type = _load_chip_smoke().CompileClock
    clock = clock_type.__new__(clock_type)   # no JAX listener needed here
    clock.spans = [(0.0, 1.0, False), (10.0, 14.0, True),
                   (12.0, 13.0, True), (13.5, 16.0, True), (20.0, 21.0, True)]
    clock.saved = [(1.0, 3.0)]
    tm = clock.setup_since(5.0)
    assert tm == dict(setup=7.0, compiles=4, cache_hits=0, saved=0)
    assert clock.setup_since(0.0)["cache_hits"] == 1


def test_round_op_shape_fixed_per_arena(monkeypatch):
    """Each chunk arena compiles one round op: its padded dirty-row count
    stays at the first round's value while the queue shrinks."""
    from repro.core.resident import ResidentBitmapArena

    seen: dict = {}
    propose = ResidentBitmapArena.propose_rows

    def spy(self, rb, *a, **k):
        out = propose(self, rb, *a, **k)
        seen.setdefault(self, []).append((rb.size, self._K))
        return out

    monkeypatch.setattr(ResidentBitmapArena, "propose_rows", spy)
    SummarizerEngine(backend="resident", T=3, seed=3).run(G)
    shrunk = [r for r in seen.values() if r[-1][0] < r[0][0]]
    assert shrunk, "no arena's dirty queue shrank across rounds"
    for rounds in seen.values():
        assert len({K for _, K in rounds}) == 1, rounds
