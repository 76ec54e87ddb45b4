"""Pruning of a summary (SLUGGER Sect. III-B4, Algorithm 3): supernodes that
do not pay for their hierarchy edges are taken out, without loss.

A round runs three steps; rounds repeat until one changes nothing, three
at most. Taking a node out attaches its children to its nearest ancestor
that stays (or makes them roots). Edges are kept as a net signed count per
pair of nodes.
1. Every non-leaf with children and no incident pair goes, all at once.
2. A root with children whose one incident pair is a non-loop edge of
   count +-1 pushes that edge down to each child and goes; of two such
   roots on one edge only the larger id does. Repeated until none is left.
3. Every non-leaf with children, deepest first and then by id, goes if that
   does not make |P+| + |P-| + |H| larger, a root only if it makes it
   smaller. Its incident pairs (each of count +-1, or it stays) move to its
   children: an edge to b to each child with b, a loop to each pair of
   children and to each child of more than one leaf. The change is judged
   on the state when its turn comes: -1 per incident pair, -(children) for
   a root and -1 otherwise, and per moved pair -1 where it cancels an
   opposite edge already there, +1 where it does not.
"""
from __future__ import annotations

import numpy as np


class _Summary:
    def __init__(self, n: int, parent: np.ndarray, edges: list):
        self.n = n
        self.parent = [int(p) for p in parent]
        self.kids = {x: [] for x in range(len(self.parent))}
        for x, p in enumerate(self.parent):
            if p >= 0:
                self.kids[p].append(x)
        self.mult: dict = {}
        self.inc: dict = {x: set() for x in range(len(self.parent))}
        for x, y, s in edges:
            self.add((x, y), s)
        leaves = np.zeros(len(self.parent), dtype=np.int64)
        leaves[:n] = 1
        for x in range(n, len(self.parent)):    # kids have smaller ids
            leaves[x] = sum(leaves[k] for k in self.kids[x])
        self.leaves = leaves.tolist()

    def add(self, key, s: int):
        x, y = min(key), max(key)
        c = self.mult.get((x, y), 0) + s
        if c:
            self.mult[(x, y)] = c
            self.inc[x].add((x, y))
            self.inc[y].add((x, y))
        else:
            self.mult.pop((x, y), None)
            self.inc[x].discard((x, y))
            self.inc[y].discard((x, y))

    def inner(self, x: int) -> bool:
        return x >= self.n and self.parent[x] > -2 and bool(self.kids[x])

    def remove(self, gone: set):
        for x in gone:
            for k in self.kids[x]:
                if k in gone:
                    continue
                p = self.parent[x]
                while p in gone:
                    p = self.parent[p]
                self.parent[k] = p
                if p >= 0:
                    self.kids[p].append(k)
        for x in gone:
            p = self.parent[x]
            if p >= 0 and p not in gone:
                self.kids[p].remove(x)
        for x in gone:
            self.parent[x] = -2
            self.kids[x] = []

    def step1(self) -> int:
        gone = {x for x in range(len(self.parent))
                if self.inner(x) and not self.inc[x]}
        self.remove(gone)
        return len(gone)

    def step2(self) -> int:
        done = 0
        while True:
            one = {}
            for x in range(len(self.parent)):
                if (self.inner(x) and self.parent[x] == -1
                        and len(self.inc[x]) == 1):
                    (key,) = self.inc[x]
                    if key[0] != key[1] and abs(self.mult[key]) == 1:
                        one[x] = key
            go = {x: key for x, key in one.items()
                  if (key[0] + key[1] - x) not in one
                  or x > key[0] + key[1] - x}
            if not go:
                return done
            for x, key in go.items():
                s = self.mult[key]
                self.add(key, -s)
                for k in self.kids[x]:
                    self.add((k, key[0] + key[1] - x), s)
            self.remove(set(go))
            done += len(go)

    def _moves(self, a: int):
        """The pairs a's incident pairs become, or None when one of them
        has a count other than +-1."""
        kids = self.kids[a]
        moves = []
        for key in self.inc[a]:
            c = self.mult[key]
            if abs(c) != 1:
                return None
            if key[0] == key[1]:
                moves += [((kids[i], kids[j]), c) for i in range(len(kids))
                          for j in range(i + 1, len(kids))]
                moves += [((k, k), c) for k in kids if self.leaves[k] > 1]
            else:
                b = key[0] + key[1] - a
                moves += [((k, b), c) for k in kids]
        return moves

    def step3(self) -> int:
        depth = {}
        order = []
        for x in range(len(self.parent) - 1, -1, -1):   # parents first
            p = self.parent[x]
            if p > -2:
                depth[x] = 0 if p == -1 else depth[p] + 1
                if self.inner(x):
                    order.append(x)
        order.sort(key=lambda x: (-depth[x], x))
        done = 0
        for a in order:
            moves = self._moves(a)
            if moves is None:
                continue
            root = self.parent[a] == -1
            d = (-len(self.kids[a]) if root else -1) - len(self.inc[a])
            for (u, v), c in moves:
                d += -1 if self.mult.get((min(u, v), max(u, v))) == -c else 1
            if d > 0 or (d == 0 and root):
                continue
            for key in list(self.inc[a]):
                self.add(key, -self.mult[key])
            for key, c in moves:
                self.add(key, c)
            self.remove({a})
            done += 1
        return done

    def edges(self) -> np.ndarray:
        rows = [(x, y, 1 if c > 0 else -1)
                for (x, y), c in self.mult.items() for _ in range(abs(c))]
        if not rows:
            return np.zeros((0, 3), dtype=np.int64)
        e = np.array(rows, dtype=np.int64)
        return e[np.lexsort((e[:, 2], e[:, 1], e[:, 0]))]


def prune(n: int, parent: np.ndarray, edges: list):
    """``(parent, edges)`` of the pruned summary: parent -2 marks a node
    taken out, edges are (x, y, sign) rows, x <= y, sorted."""
    s = _Summary(n, parent, edges)
    for _ in range(3):
        if not (s.step1() + s.step2() + s.step3()):
            break
    return np.asarray(s.parent, dtype=np.int64), s.edges()
