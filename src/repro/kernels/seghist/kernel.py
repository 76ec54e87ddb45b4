"""Pallas TPU kernel: tiled segment histogram (one-hot compare-and-sum).

Role in the system: the batched emission DP (`core/encode_batched.py`)
classifies every pair state of a level as empty / full / mixed from its
subedge membership count. The counts are a histogram of per-edge state ids —
this kernel computes it as a tiled one-hot reduction: each (segment-block,
edge-block) grid step broadcasts a (BE, 1) id column against a (1, BS) iota
of segment ids and accumulates the match count, the same compare-and-reduce
layout the MXU one-hot-matmul histogram trick uses. Mirrors the
bitset-Jaccard kernel wiring (grid accumulation over the streamed axis,
interpret-mode default off-TPU).

Padding contract: callers pad the id array with -1, which matches no
segment block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import default_interpret


def _seghist_block(seg_ref, out_ref, *, block_s: int):
    j = pl.program_id(0)  # segment block
    k = pl.program_id(1)  # edge block (streamed, accumulated)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    seg = seg_ref[...]  # (1, BE) int32, padded entries are -1
    sid = j * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    hits = (seg[0, :, None] == sid[0, None, :]).astype(jnp.int32)  # (BE, BS)
    out_ref[...] += hits.sum(axis=0, keepdims=True)


def segment_histogram_kernel(seg: jax.Array, num_segments: int,
                             block_s: int = 512, block_e: int = 1024,
                             interpret=None) -> jax.Array:
    """seg: (E,) int32 ids in [0, num_segments) or -1 -> (num_segments,) int32.

    Does not compile for TPU yet; ``interpret`` defaults to interpreting
    off-TPU only, so a call on the chip fails instead of silently
    interpreting."""
    if interpret is None:
        interpret = default_interpret()
    E = seg.shape[0]
    S = int(num_segments)
    bs = min(block_s, max(S, 1))
    be = min(block_e, max(E, 1))
    Ep = pl.cdiv(max(E, 1), be) * be
    Sp = pl.cdiv(max(S, 1), bs) * bs
    seg2 = jnp.full((1, Ep), -1, dtype=jnp.int32).at[0, :E].set(seg.astype(jnp.int32))
    grid = (Sp // bs, Ep // be)
    out = pl.pallas_call(
        functools.partial(_seghist_block, block_s=bs),
        grid=grid,
        in_specs=[pl.BlockSpec((1, be), lambda j, k: (0, k))],
        out_specs=pl.BlockSpec((1, bs), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, Sp), jnp.int32),
        interpret=interpret,
    )(seg2)
    return out[0, :S]
