"""Graph500 Kronecker generator, as the Graph500 specification's reference
code writes it (kronecker_generator.m): 2^scale vertices, edge_factor *
2^scale edges drawn bit by bit from the initiator [[A, B], [C, D]], then
the vertex labels randomly permuted. The edges come from the
configuration's ``structure_seed`` and the permutation from the seed it
is given, so every seed gets the same graph with its vertices in another
order. The shuffle of the edge list that the
reference also makes is left out: the summarizer takes the graph in CSR
form, where edge order does not exist. Self-loops and duplicates are
dropped when the CSR is built."""
from __future__ import annotations

import numpy as np


def generate(cfg: dict, seed: int):
    """Returns ``(n, edges)`` with edges an (m, 2) int64 array."""
    scale = int(cfg["scale"])
    a, b, c, _d = (float(x) for x in cfg["initiator"])
    n = 1 << scale
    m = int(cfg["edge_factor"]) * n
    rng = np.random.default_rng(int(cfg["structure_seed"]))
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ij = np.zeros((2, m), dtype=np.int64)
    for ib in range(scale):
        ii_bit = rng.random(m) > ab
        jj_bit = rng.random(m) > np.where(ii_bit, c_norm, a_norm)
        ij[0] += ii_bit.astype(np.int64) << ib
        ij[1] += jj_bit.astype(np.int64) << ib
    if cfg.get("permute", True):
        ij = np.random.default_rng(seed).permutation(n)[ij]
    return n, ij.T.copy()
