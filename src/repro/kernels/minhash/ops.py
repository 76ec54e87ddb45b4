"""Jit'd public wrapper for the minhash kernel: CSR graph -> root shingles."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.minhash import ref
from repro.kernels.minhash.kernel import rowmin_hash_kernel


def pack_adjacency(indptr: np.ndarray, indices: np.ndarray, width: int = 128):
    """Pack CSR rows into fixed-width uint32 rows (TPU-regular layout).

    High-degree nodes span ceil(deg/width) rows; ``row_owner`` maps each packed
    row back to its node. Includes the node itself (shingles hash N(u) ∪ {u}).
    """
    n = indptr.shape[0] - 1
    deg1 = np.diff(indptr) + 1  # + self
    rows_per = -(-deg1 // width)  # ceil; deg1 >= 1 so always >= 1
    owners = np.repeat(np.arange(n, dtype=np.int64), rows_per)
    R = int(rows_per.sum())
    out = np.full((R, width), np.uint32(0xFFFFFFFF), dtype=np.uint32)
    row0 = np.cumsum(rows_per) - rows_per
    # flat [u | N(u)] value stream + one scatter — no per-node Python loop
    total = int(deg1.sum())
    node_of = np.repeat(np.arange(n, dtype=np.int64), deg1)
    start_v = np.cumsum(deg1) - deg1
    off = np.arange(total, dtype=np.int64) - start_v[node_of]
    vals = np.empty(total, dtype=np.uint32)
    vals[off == 0] = np.arange(n, dtype=np.uint32)
    vals[off > 0] = np.asarray(indices, dtype=np.uint32)
    out[row0[node_of] + off // width, off % width] = vals
    return out, owners


def node_shingles(nbr_rows: jax.Array, row_owner: np.ndarray, n: int,
                  a: int, b: int, use_kernel: bool = True,
                  interpret=None) -> jax.Array:
    """Per-node shingle = min hash over N(u) ∪ {u}."""
    if use_kernel:
        mins = rowmin_hash_kernel(nbr_rows, a, b, interpret=interpret)
    else:
        mins = ref.rowmin_hash(nbr_rows, a, b)
    seg = jax.ops.segment_min(mins, jnp.asarray(row_owner), num_segments=n)
    return seg


def root_shingles(node_sh: jax.Array, root_of: jax.Array, n_ids: int) -> jax.Array:
    """Root shingle = min over member nodes (segment-min over root ids)."""
    return jax.ops.segment_min(node_sh, root_of, num_segments=n_ids)
