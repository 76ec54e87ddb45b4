"""The benchmark's graph generators and window arithmetic, on the CPU."""
from __future__ import annotations

import itertools
from pathlib import Path

import numpy as np
import pytest

from chipbench.bench import load_module, read_json
from chipbench.reference.graph import Graph
from chipbench.window import rate, run_window

HERE = Path(__file__).resolve().parent
BIG_SEED = 2**31 + 12345


def _gen(name: str):
    return load_module(HERE / "generators" / f"{name}.py")


def _small(config: str, scale: int = 10) -> dict:
    return dict(read_json(HERE / "configs" / f"{config}.json"), scale=scale)


@pytest.mark.parametrize("scale", [8, 10])
def test_same_seed_same_graph_other_seed_relabelled(scale):
    cfg = _small("graph500", scale)
    gen = _gen(cfg["generator"])
    n1, e1 = gen.generate(cfg, BIG_SEED)
    n2, e2 = gen.generate(cfg, BIG_SEED)
    n3, e3 = gen.generate(cfg, BIG_SEED + 1)
    assert n1 == n2 == n3
    assert np.array_equal(e1, e2)
    assert not np.array_equal(e1, e3)
    assert e1.dtype == np.int64 and e1.shape[1] == 2
    assert e1.min() >= 0 and e1.max() < n1
    # another seed gives the same graph under another labelling: the same
    # degree sequence, and the unpermuted graphs are equal
    g1, g3 = Graph.from_edges(n1, e1), Graph.from_edges(n3, e3)
    assert g1.m == g3.m
    assert np.array_equal(np.sort(np.diff(g1.indptr)),
                          np.sort(np.diff(g3.indptr)))
    plain = dict(cfg, permute=False)
    assert np.array_equal(gen.generate(plain, BIG_SEED)[1],
                          gen.generate(plain, BIG_SEED + 1)[1])


def test_kronecker_follows_the_graph500_parameters():
    cfg = _small("graph500")
    n, e = _gen("kronecker").generate(cfg, BIG_SEED)
    assert n == 1 << 10 and e.shape[0] == 16 * n
    deg = np.bincount(e.ravel(), minlength=n)
    # skewed: the initiator concentrates edges, so the busiest vertex has
    # far more than the mean of 32 ends
    assert deg.max() > 10 * deg.mean()
    # the labels are permuted: unpermuted, vertex 0 is the densest
    plain = _gen("kronecker").generate(dict(cfg, permute=False), BIG_SEED)[1]
    assert np.bincount(plain.ravel(), minlength=n).argmax() == 0
    assert deg.argmax() != 0


def test_graph500_has_the_stated_size():
    cfg = read_json(HERE / "configs" / "graph500.json")
    n, e = _gen("kronecker").generate(cfg, BIG_SEED)
    assert n == 1 << 15 and e.shape[0] == 16 * n
    assert Graph.from_edges(n, e).m == 441_430


def test_window_takes_all_work_over_all_time():
    ticks = itertools.count(0.0, 3.0)   # every job takes 3 s
    results, window_s, (t0, t1) = run_window(
        lambda i: i, 10.0, clock=lambda: next(ticks))
    # closes at the end of the first job that ends at or after 10 s
    assert results == [0, 1, 2, 3] and window_s == 12.0
    assert (t0, t1) == (0.0, 12.0)
    assert rate(1_000, 4, window_s) == pytest.approx(1_000 * 4 / 12.0)
    # only the jobs that finished correct count as work
    assert rate(1_000, 3, window_s) == pytest.approx(250.0)


def test_window_closes_on_a_whole_pass():
    ticks = itertools.count(0.0, 3.0)   # every job takes 3 s
    results, window_s, _ = run_window(
        lambda i: i, 10.0, clock=lambda: next(ticks), every=3)
    # 12 s is past 10 s, but the pass of three jobs ends only at 18 s
    assert results == [0, 1, 2, 3, 4, 5] and window_s == 18.0
    results, window_s, _ = run_window(
        lambda i: i, 0.0, clock=lambda: next(ticks), every=2)
    assert results == [0, 1] and window_s == 6.0


def test_window_of_zero_seconds_runs_one_job():
    ticks = itertools.count(0.0, 5.0)
    results, window_s, _ = run_window(lambda i: i, 0.0,
                                      clock=lambda: next(ticks))
    assert results == [0] and window_s == 5.0
