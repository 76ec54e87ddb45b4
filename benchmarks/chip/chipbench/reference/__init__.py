"""Plain host reference of SLUGGER, written from the rules the summarizer
states for a seed: the merge forest (`forest`), the least encoding over it
(`emit`) and pruning (`prune`). It imports nothing of the program and takes
nothing the program made; it shares no code with it either, so a fault in
the program's own host logic is not repeated here.

    out = summarize(n, edges, T=2, seed=seed)
    out["parent"], out["edges"]   # pruned forest and (k, 3) signed edges
"""
from __future__ import annotations

import numpy as np

from .emit import encode
from .forest import merge_forest
from .graph import Graph
from .prune import prune


def encode_and_prune(g: Graph, forest: np.ndarray):
    """The least encoding of g over a binary merge forest, then pruning."""
    el = g.edge_list().astype(np.int64)
    return prune(g.n, forest, encode(g.n, forest, el[:, 0], el[:, 1]))


def summarize(n: int, edges: np.ndarray, T: int, seed: int,
              precision: str = "exact") -> dict:
    """The summary the specified algorithm gives for ``seed``;
    ``precision="bf16"`` is the control, with the Jaccard ranking and the
    Saving rounded to bfloat16."""
    g = Graph.from_edges(n, edges)
    forest = merge_forest(g.n, g.indptr, g.indices.astype(np.int64), T, seed,
                          precision=precision)
    parent, sedges = encode_and_prune(g, forest)
    return {"parent": parent, "edges": sedges, "forest": forest,
            "graph": g}
