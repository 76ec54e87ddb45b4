"""The comparison that decides ``correct`` for a summarize cell.

Every job the window ran is held to the reference: its pruned forest
(``parent``) and its signed summary edges must equal the reference's bit
for bit, and decoding its summary must give back exactly the input graph.
Losslessness alone would pass any forest, so the equality is what catches a
changed merge decision. Every compared number is a count with the limit 0.
"""
from __future__ import annotations

import numpy as np

LIMITS = {"parent_mismatch": 0, "edge_mismatch": 0, "lossless_mismatch": 0,
          "degradations": 0}


def parent_mismatch(parent: np.ndarray, ref_parent: np.ndarray) -> int:
    """Positions whose forest parent differs, plus any length difference."""
    k = min(parent.size, ref_parent.size)
    return int(np.count_nonzero(parent[:k] != ref_parent[:k])
               + abs(parent.size - ref_parent.size))


def _rows(edges: np.ndarray) -> np.ndarray:
    e = np.ascontiguousarray(np.asarray(edges, dtype=np.int64).reshape(-1, 3))
    return e.view([("x", np.int64), ("y", np.int64), ("s", np.int64)]).ravel()


def edge_mismatch(edges: np.ndarray, ref_edges: np.ndarray) -> int:
    """Rows in one signed edge multiset and not in the other."""
    a, b = np.sort(_rows(edges)), np.sort(_rows(ref_edges))
    if a.size == b.size and np.array_equal(a, b):
        return 0
    ua, ca = np.unique(a, return_counts=True)
    ub, cb = np.unique(b, return_counts=True)
    keys = np.union1d(ua, ub)
    na = np.zeros(keys.size, dtype=np.int64)
    nb = np.zeros(keys.size, dtype=np.int64)
    na[np.searchsorted(keys, ua)] = ca
    nb[np.searchsorted(keys, ub)] = cb
    return int(np.abs(na - nb).sum())


def _leaf_csr(parent: np.ndarray, n: int):
    """Leaves of every supernode as CSR ``(ptr, leaves)``: each leaf is
    listed under itself and under every ancestor."""
    node = np.arange(n, dtype=np.int64)
    owners, leaves = [node], [node]
    leaf = node
    cur = parent[node]
    while True:
        up = cur >= 0
        if not up.any():
            break
        leaf, cur = leaf[up], cur[up]
        owners.append(cur)
        leaves.append(leaf)
        cur = parent[cur]
    owners = np.concatenate(owners)
    leaves = np.concatenate(leaves)
    order = np.argsort(owners, kind="stable")
    ptr = np.zeros(parent.size + 1, dtype=np.int64)
    np.cumsum(np.bincount(owners, minlength=parent.size), out=ptr[1:])
    return ptr, leaves[order]


def decode(n: int, parent: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Sorted keys ``u * n + v`` (u < v) of the graph a summary encodes: a
    leaf pair is an edge iff the signed edges between its ancestor sets
    (each leaf included) sum above zero."""
    parent = np.asarray(parent, dtype=np.int64)
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 3)
    if edges.shape[0] == 0:
        return np.zeros(0, dtype=np.int64)
    ptr, leaves = _leaf_csr(parent, n)
    size = np.diff(ptr)
    X, Y, S = edges[:, 0], edges[:, 1], edges[:, 2]
    keys, weights = [], []
    cross = X != Y
    if cross.any():
        x, y, s = X[cross], Y[cross], S[cross]
        sy = size[y]
        lens = size[x] * sy
        total = int(lens.sum())
        local = np.arange(total, dtype=np.int64) - np.repeat(
            np.cumsum(lens) - lens, lens)
        wid = np.repeat(sy, lens)
        u = leaves[np.repeat(ptr[x], lens) + local // wid]
        v = leaves[np.repeat(ptr[y], lens) + local % wid]
        keys.append(np.minimum(u, v) * n + np.maximum(u, v))
        weights.append(np.repeat(s, lens))
    loop = ~cross
    if loop.any():
        x, s = X[loop], S[loop]
        for k in np.unique(size[x]):
            if k < 2:
                continue
            iu, iv = np.triu_indices(int(k), k=1)
            sel = size[x] == k
            base = np.repeat(ptr[x[sel]], iu.size)
            u = leaves[base + np.tile(iu, int(sel.sum()))]
            v = leaves[base + np.tile(iv, int(sel.sum()))]
            keys.append(np.minimum(u, v) * n + np.maximum(u, v))
            weights.append(np.repeat(s[sel], iu.size))
    if not keys:
        return np.zeros(0, dtype=np.int64)
    keys = np.concatenate(keys)
    uniq, inv = np.unique(keys, return_inverse=True)
    tot = np.bincount(inv, weights=np.concatenate(weights))
    return uniq[tot > 0]


def lossless_mismatch(graph, parent: np.ndarray, edges: np.ndarray) -> int:
    """Leaf pairs on which the decoded summary and the input graph differ."""
    n = graph.n
    el = graph.edge_list().astype(np.int64)
    want = el[:, 0] * n + el[:, 1]
    got = decode(n, parent, edges)
    if got.size == want.size and np.array_equal(got, want):
        return 0
    return int(np.setxor1d(got, want, assume_unique=True).size)


def compare(jobs: list, ref: dict) -> list:
    """Per job ``(parent, edges, degradations)``, its counts against the
    reference output; each count is judged against `LIMITS`. Identical
    outputs are decoded once."""
    graph = ref["graph"]
    decoded: dict = {}
    out = []
    for parent, edges, degradations in jobs:
        key = (parent.tobytes(), np.asarray(edges).tobytes())
        if key not in decoded:
            decoded[key] = lossless_mismatch(graph, parent, edges)
        out.append({"parent_mismatch": parent_mismatch(parent, ref["parent"]),
                    "edge_mismatch": edge_mismatch(edges, ref["edges"]),
                    "lossless_mismatch": decoded[key],
                    "degradations": int(degradations)})
    return out


def total(per_job: list) -> dict:
    return {name: sum(f[name] for f in per_job) for name in LIMITS}


def verdict(found: dict) -> bool:
    return all(found[name] <= limit for name, limit in LIMITS.items())


def render(found: dict) -> dict:
    """``{name: {"value": v, "limit": l}}`` in `LIMITS` order."""
    return {name: {"value": found[name], "limit": LIMITS[name]}
            for name in LIMITS}
