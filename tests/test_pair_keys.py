"""The packed int64 pair key (`summary_ir.pair_key`) against the multi-key
np.lexsort it replaces: `group_pairs`, `canon_edges` and
`pruning._pair_lookup` give the lexsort's answer element for element, on
id spans below, at and past the key's 2**31 bound (past it they take the
lexsort themselves), and the `post.*` tallies say which path each took."""
import numpy as np
import pytest

from repro.core.engine import SummarizerEngine
from repro.core.pruning import _pair_lookup
from repro.core.spans import COUNTS
from repro.core.summary_ir import (PAIR_KEY_SPAN_LIMIT, canon_edges,
                                   group_pairs)
from repro.graphs import generators as GG

# largest id of a case: the span (max id + 1) below, at and past the bound
MAX_IDS = {
    "small": 999,
    "below": PAIR_KEY_SPAN_LIMIT - 2,
    "at": PAIR_KEY_SPAN_LIMIT - 1,
    "above": PAIR_KEY_SPAN_LIMIT,
}
SIZES = (0, 1, 1000, 100_000)


def _pairs(n: int, max_id: int, seed: int):
    """``n`` (a, b, sign) rows over [0, max_id]: drawn from a pool of about
    n/4 pairs, so pairs repeat, with self pairs, both signs, and the
    largest id present."""
    rng = np.random.default_rng(seed)
    pool = max(1, n // 4)
    ids = rng.integers(0, max_id + 1, size=(pool, 2), dtype=np.int64)
    ids[: pool // 8, 1] = ids[: pool // 8, 0]  # self pairs
    ids[-1, 0] = max_id
    pick = rng.integers(0, pool, size=n)
    a, b = ids[pick, 0], ids[pick, 1]
    sign = np.where(rng.random(n) < 0.5, -1, 1).astype(np.int64)
    return a, b, sign


def _fallbacks(fn):
    before = COUNTS.snapshot().get("post.sort_fallbacks", 0)
    out = fn()
    return out, COUNTS.snapshot().get("post.sort_fallbacks", 0) - before


def _check_group_pairs(a, b, sign):
    ref = np.lexsort((b, a))
    sa, sb = a[ref], b[ref]
    head = np.ones(a.size, dtype=bool)
    head[1:] = (sa[1:] != sa[:-1]) | (sb[1:] != sb[:-1])
    (order, starts), fell = _fallbacks(lambda: group_pairs(a, b))
    assert np.array_equal(a[order], sa) and np.array_equal(b[order], sb)
    assert np.array_equal(starts, np.flatnonzero(head))
    # stable keeps the lexsort's permutation itself
    order_s, starts_s = group_pairs(a, b, stable=True)
    assert np.array_equal(order_s, ref)
    assert np.array_equal(starts_s, starts)
    return fell


def _check_canon_edges(a, b, sign):
    rows = np.stack([a, b, sign], axis=1)
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    ref = np.stack([lo, hi, sign], axis=1)[np.lexsort((sign, hi, lo))]
    got, fell = _fallbacks(lambda: canon_edges(rows))
    assert got.dtype == np.int64 and got.shape == (a.size, 3)
    assert np.array_equal(got, ref)
    return fell


def _lookup_oracle(bex, bey, bec, qx, qy):
    """The lexsort alignment of queries next to their base row."""
    nq = qx.size
    if nq == 0 or bex.size == 0:
        return np.zeros(nq, dtype=np.int64)
    allx, ally = np.concatenate([bex, qx]), np.concatenate([bey, qy])
    isq = np.r_[np.zeros(bex.size, np.int64), np.ones(nq, np.int64)]
    order = np.lexsort((isq, ally, allx))
    sx, sy = allx[order], ally[order]
    head = np.ones(allx.size, dtype=bool)
    head[1:] = (sx[1:] != sx[:-1]) | (sy[1:] != sy[:-1])
    gid = np.cumsum(head) - 1
    val = np.where(isq == 0, np.r_[bec, np.zeros(nq, np.int64)], 0)[order]
    gsum = np.zeros(gid[-1] + 1, dtype=np.int64)
    np.add.at(gsum, gid, val)
    out = np.empty(allx.size, dtype=np.int64)
    out[order] = gsum[gid]
    return out[bex.size:]


def _check_pair_lookup(a, b, sign):
    # base: the distinct pairs, shuffled, with multiplicities of both signs;
    # queries: every row (repeated pairs) and the swapped rows (mostly absent)
    _, first = np.unique(np.stack([a, b], axis=1), axis=0, return_index=True)
    rng = np.random.default_rng(a.size)
    first = rng.permutation(first)
    bex, bey = a[first], b[first]
    bec = sign[first] * rng.integers(1, 4, size=first.size)
    qx, qy = np.r_[a, b], np.r_[b, a]
    ref = _lookup_oracle(bex, bey, bec, qx, qy)
    got, fell = _fallbacks(lambda: _pair_lookup(bex, bey, bec, qx, qy))
    assert np.array_equal(got, ref)
    # the same base handed on sorted, as the pruner's passes hand it on
    srt = np.lexsort((bey, bex))
    got = _pair_lookup(bex[srt], bey[srt], bec[srt], qx, qy)
    assert np.array_equal(got, ref)
    return fell


CHECKS = {
    "group_pairs": _check_group_pairs,
    "canon_edges": _check_canon_edges,
    "pair_lookup": _check_pair_lookup,
}


@pytest.mark.parametrize("span", list(MAX_IDS))
@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("fn", list(CHECKS))
def test_packed_key_order_is_the_lexsort_order(fn, n, span):
    a, b, sign = _pairs(n, MAX_IDS[span], seed=n + len(span))
    fell = CHECKS[fn](a, b, sign)
    if n and span in ("at", "above"):
        assert fell >= 1  # past the key's bound: the lexsort itself
    else:
        assert fell == 0


def test_group_pairs_past_the_bound_counts_one_fallback():
    a = np.array([PAIR_KEY_SPAN_LIMIT, 3, PAIR_KEY_SPAN_LIMIT], dtype=np.int64)
    b = np.array([1, 2, 1], dtype=np.int64)
    snap = COUNTS.snapshot()
    order, starts = group_pairs(a, b)
    d = COUNTS.delta_since(snap)
    assert d == {"post.sort_fallbacks": 1}
    assert np.array_equal(a[order], [3, PAIR_KEY_SPAN_LIMIT,
                                     PAIR_KEY_SPAN_LIMIT])
    assert np.array_equal(starts, [0, 1])


def test_canon_edges_with_a_sign_past_one_falls_back():
    rows = np.array([[4, 2, 2], [2, 4, 1], [1, 1, -1]], dtype=np.int64)
    snap = COUNTS.snapshot()
    got = canon_edges(rows)
    assert COUNTS.delta_since(snap) == {"post.sort_fallbacks": 1}
    assert np.array_equal(got, [[1, 1, -1], [2, 4, 1], [2, 4, 2]])


@pytest.mark.parametrize("partitions", (1, 4))
def test_engine_counts_packed_key_sorts(partitions):
    g = GG.rmat(9, edge_factor=16, seed=4)
    eng = SummarizerEngine(backend="numpy", partitions=partitions, T=2,
                           seed=3)
    s = eng.run(g)
    assert eng.stats["post.sorts"] > 0
    assert eng.stats["post.sort_fallbacks"] == 0.0
    assert s.validate_lossless(g)


@pytest.mark.parametrize("partitions", (1, 4))
def test_lexsort_fallback_gives_the_same_summary(partitions, monkeypatch):
    """With the key's bound pulled down to 1 every pair list takes the
    lexsort: emission and pruning must give the packed path's summary."""
    from repro.core import summary_ir

    g = GG.rmat(9, edge_factor=16, seed=5)
    packed = SummarizerEngine(backend="numpy", partitions=partitions, T=2,
                              seed=7).run(g)
    monkeypatch.setattr(summary_ir, "PAIR_KEY_SPAN_LIMIT", 1)
    eng = SummarizerEngine(backend="numpy", partitions=partitions, T=2,
                           seed=7)
    s = eng.run(g)
    assert eng.stats["post.sorts"] == 0.0
    assert eng.stats["post.sort_fallbacks"] > 0
    assert np.array_equal(s.parent, packed.parent)
    assert np.array_equal(s.edges, packed.edges)
