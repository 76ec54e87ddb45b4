"""Simple undirected graph in CSR form (the reference's own copy).

Invariants: no self-loops, no duplicate edges, symmetric, rows sorted.
"""
from __future__ import annotations

import numpy as np


class Graph:
    __slots__ = ("n", "indptr", "indices")

    def __init__(self, n: int, indptr: np.ndarray, indices: np.ndarray):
        self.n = int(n)
        self.indptr = np.asarray(indptr, dtype=np.int64)
        self.indices = np.asarray(indices, dtype=np.int32)

    @staticmethod
    def from_edges(n: int, edges: np.ndarray) -> "Graph":
        """Build from an (m, 2) array of (possibly dirty) edges: drops
        self-loops and duplicates, symmetrizes, sorts rows."""
        edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
        if edges.size:
            edges = edges[edges[:, 0] != edges[:, 1]]
        if edges.size == 0:
            return Graph(n, np.zeros(n + 1, dtype=np.int64),
                         np.zeros(0, dtype=np.int32))
        lo = np.minimum(edges[:, 0], edges[:, 1])
        hi = np.maximum(edges[:, 0], edges[:, 1])
        key = np.unique(lo * n + hi)
        lo, hi = key // n, key % n
        src = np.concatenate([lo, hi])
        dst = np.concatenate([hi, lo])
        order = np.lexsort((dst, src))
        src, dst = src[order], dst[order]
        indptr = np.zeros(n + 1, dtype=np.int64)
        np.add.at(indptr, src + 1, 1)
        np.cumsum(indptr, out=indptr)
        return Graph(n, indptr, dst.astype(np.int32))

    @property
    def m(self) -> int:
        return int(self.indices.shape[0] // 2)

    def edge_list(self) -> np.ndarray:
        """(m, 2) array with u < v per row, sorted."""
        src = np.repeat(np.arange(self.n, dtype=np.int32),
                        np.diff(self.indptr))
        mask = src < self.indices
        return np.stack([src[mask], self.indices[mask]], axis=1)
