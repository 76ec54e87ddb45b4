"""The signed summary edges of a graph over a binary merge forest, by the
encoding recurrence of SLUGGER (Sect. III-B3), one pair of roots at a time.

A state is a pair of supernodes (x, y), x <= y, or a supernode with itself;
its subedges are the graph's edges between their leaves, ``cnt`` of the
``poss`` leaf pairs. A state is pure when cnt is 0 or poss, else mixed. With
no edge placed above it (parity 0) a full state places a + edge and an empty
one nothing; under a placed edge (parity 1) a full state places nothing and
an empty one, if it has leaf pairs, a - edge. A mixed state either descends
into the pairs of its children (both children of a side that has them; of
a self state the two children with themselves and with each other), or
places its own edge and flips the parity beneath it: with E0 and E1 the
least edges under parity 0 and 1, it descends on a tie. A pure self state
over exactly two leaves places its edge at that leaf pair.
"""
from __future__ import annotations

import numpy as np


class _Tree:
    def __init__(self, parent: np.ndarray, n: int):
        self.n = n
        ids = parent.size
        self.kids = [[] for _ in range(ids)]
        for x, p in enumerate(parent.tolist()):
            if p >= 0:
                self.kids[p].append(x)
        for k in self.kids:
            if len(k) not in (0, 2):
                raise ValueError("the merge forest is not binary")
        # leaves numbered left to right; a node owns [lo, hi)
        self.lo = np.zeros(ids, dtype=np.int64)
        self.hi = np.zeros(ids, dtype=np.int64)
        self.pos = np.zeros(n, dtype=np.int64)
        nxt = 0
        for r in np.flatnonzero(parent == -1).tolist():
            stack = [(r, False)]
            while stack:
                x, done = stack.pop()
                if done:
                    self.hi[x] = nxt
                    continue
                self.lo[x] = nxt
                if not self.kids[x]:
                    self.pos[x] = nxt
                    nxt += 1
                    self.hi[x] = nxt
                    continue
                stack.append((x, True))
                stack.extend((k, False) for k in reversed(self.kids[x]))
        self.size = (self.hi - self.lo).tolist()
        self.lo = self.lo.tolist()

    def poss(self, x: int, y: int) -> int:
        s = self.size[x]
        return s * (s - 1) // 2 if x == y else s * self.size[y]


class _State:
    __slots__ = ("x", "y", "cnt", "poss", "split", "kids", "e0", "e1")


def _state(t: _Tree, x: int, y: int, pu: list, pv: list) -> _State:
    """The state (x, y) over its subedges, given as the leaf positions of
    their x-side and y-side ends; a mixed one holds its subedges split by
    the pair of children they fall in."""
    st = _State()
    st.x, st.y, st.cnt, st.poss = x, y, len(pu), t.poss(x, y)
    st.split = st.kids = None
    if st.cnt == 0 or st.cnt == st.poss:
        st.e0 = 1 if st.cnt > 0 else 0
        st.e1 = 1 if st.cnt == 0 and st.poss > 0 else 0
        return st
    xs, ys = t.kids[x] or [x], t.kids[y] or [y]
    if x == y:
        k0, k1 = xs
        sub = {(k0, k0): ([], []), (k1, k1): ([], []), (k0, k1): ([], [])}
    else:
        sub = {(min(a, b), max(a, b)): ([], []) for a in xs for b in ys}
    cut_x = t.lo[xs[1]] if len(xs) == 2 else None
    cut_y = t.lo[ys[1]] if len(ys) == 2 else None
    for a, b in zip(pu, pv):
        ca = xs[0] if cut_x is None or a < cut_x else xs[1]
        cb = ys[0] if cut_y is None or b < cut_y else ys[1]
        if ca <= cb:
            side = sub[(ca, cb)]
            side[0].append(a)
            side[1].append(b)
        else:
            side = sub[(cb, ca)]
            side[0].append(b)
            side[1].append(a)
    st.split = sub
    return st


def _solve(t: _Tree, root: _State):
    """E0 and E1 of every state beneath ``root``, children first."""
    order, stack = [], [root]
    while stack:
        st = stack.pop()
        order.append(st)
        if st.split is not None:
            st.kids = [_state(t, a, b, lo, hi)
                       for (a, b), (lo, hi) in st.split.items()]
            st.split = None
            stack.extend(st.kids)
    for st in reversed(order):
        if st.kids is not None:
            d0 = sum(k.e0 for k in st.kids)
            d1 = sum(k.e1 for k in st.kids)
            st.e0, st.e1 = min(d0, 1 + d1), min(d1, 1 + d0)


def _place(t: _Tree, x: int, y: int, sign: int, out: list):
    if x == y and t.kids[x] and all(not t.kids[k] for k in t.kids[x]):
        x, y = t.kids[x]
    out.append((min(x, y), max(x, y), sign))


def _emit(t: _Tree, root: _State, out: list):
    stack = [(root, 0)]
    while stack:
        st, par = stack.pop()
        if st.kids is None:
            if st.cnt > 0 and par == 0:
                _place(t, st.x, st.y, 1, out)
            elif st.cnt == 0 and st.poss > 0 and par == 1:
                _place(t, st.x, st.y, -1, out)
            continue
        d0 = sum(k.e0 for k in st.kids)
        d1 = sum(k.e1 for k in st.kids)
        if (d0 <= 1 + d1) if par == 0 else (d1 <= 1 + d0):
            stack.extend((k, par) for k in st.kids)
        else:
            out.append((st.x, st.y, 1 if par == 0 else -1))
            stack.extend((k, 1 - par) for k in st.kids)


def encode(n: int, parent: np.ndarray, u: np.ndarray, v: np.ndarray) -> list:
    """Signed edges ``(x, y, sign)``, x <= y, of the least encoding of the
    edges (u, v) over the forest."""
    t = _Tree(np.asarray(parent, dtype=np.int64), n)
    par = np.asarray(parent, dtype=np.int64)
    root = np.arange(par.size, dtype=np.int64)
    while True:
        up = par[root]
        if (up < 0).all():
            break
        root = np.where(up >= 0, up, root)
    ru, rv = root[u], root[v]
    swap = ru > rv
    ru, rv = np.where(swap, rv, ru), np.where(swap, ru, rv)
    pu, pv = t.pos[np.where(swap, v, u)], t.pos[np.where(swap, u, v)]
    order = np.lexsort((rv, ru))
    ru, rv, pu, pv = ru[order], rv[order], pu[order], pv[order]
    cut = np.flatnonzero((ru[1:] != ru[:-1]) | (rv[1:] != rv[:-1])) + 1
    starts = np.concatenate([[0], cut]).astype(np.int64)
    ends = np.concatenate([cut, [ru.size]]).astype(np.int64)
    out: list = []
    for s, e in zip(starts.tolist(), ends.tolist()):
        x, y = int(ru[s]), int(rv[s])
        if e - s == t.poss(x, y):
            _place(t, x, y, 1, out)
            continue
        root = _state(t, x, y, pu[s:e].tolist(), pv[s:e].tolist())
        _solve(t, root)
        _emit(t, root, out)
    return out
