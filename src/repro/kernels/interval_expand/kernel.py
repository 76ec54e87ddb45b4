"""Pallas TPU kernel: signed interval-membership counts.

Role in the system: the batched summary-query engine (`core/query_batch.py`)
answers ``neighbors``/``edge_exists`` on the packed serving artifact by
counting, for every probe position p of a query, the signed number of
incident-edge intervals that contain p:

    count[b, p] = sum_e sign[b, e] * [lo[b, e] <= pos[b, p] < hi[b, e]]

This is the membership-count inner loop of the interval sweep — for
``edge_exists`` the probes are the partner positions, for ``neighbors`` they
are the 2·deg interval boundaries (the count at a boundary equals the sweep's
running sum over the half-open range it opens). The kernel follows the
`seghist` layout: a (query-block, probe-block, interval-block) grid where
each step transposes its (8, BE) interval tiles to columns, broadcasts each
query's (BE, 1) interval column against its (1, BP) probe row, and
accumulates compare-and-sum hits over the streamed interval axis.

Padding contract: callers pad intervals with lo == hi == 0 (empty, matches no
probe) and probes with -1 (contained in no interval, since lo >= 0).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import default_interpret


_ROWS = 8  # queries per block: the sublane tile of a 32-bit array


def _interval_count_block(lo_ref, hi_ref, sg_ref, pos_ref, out_ref):
    k = pl.program_id(2)  # interval block (streamed, accumulated)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # intervals turn to columns (BE, 8) so each query's intervals broadcast
    # against its probe row (1, BP) with no per-element transposes
    lo = lo_ref[...].T
    hi = hi_ref[...].T
    sg = sg_ref[...].T   # padded entries are 0
    p = pos_ref[...]     # (8, BP) int32, padded probes are -1
    for r in range(p.shape[0]):
        pr = p[r:r + 1, :]
        inside = (lo[:, r:r + 1] <= pr) & (pr < hi[:, r:r + 1])   # (BE, BP)
        out_ref[r:r + 1, :] += jnp.where(inside, sg[:, r:r + 1], 0).sum(
            axis=0, keepdims=True)


def interval_count_kernel(lo: jax.Array, hi: jax.Array, sign: jax.Array,
                          pos: jax.Array, block_p: int = 256,
                          block_e: int = 256, interpret=None) -> jax.Array:
    """(B, E) int32 intervals + (B, P) int32 probes -> (B, P) int32 counts.

    The grid walks (8-query block, probe block, interval block); on TPU the
    probe and interval blocks are multiples of 128 (or the whole padded
    width). B, E and P are padded here to whole blocks under the padding
    contract, so any shape is accepted.
    """
    if interpret is None:
        interpret = default_interpret()
    B, E = lo.shape
    P = pos.shape[1]
    bp = min(block_p, max(P, 1))
    be = min(block_e, max(E, 1))
    Bp = pl.cdiv(max(B, 1), _ROWS) * _ROWS
    Ep = pl.cdiv(max(E, 1), be) * be
    Pp = pl.cdiv(max(P, 1), bp) * bp

    def _pad(a, width, fill):
        return jnp.full((Bp, width), fill, dtype=jnp.int32).at[
            :B, : a.shape[1]].set(a.astype(jnp.int32))

    lo2, hi2, sg2 = _pad(lo, Ep, 0), _pad(hi, Ep, 0), _pad(sign, Ep, 0)
    pos2 = _pad(pos, Pp, -1)
    grid = (Bp // _ROWS, Pp // bp, Ep // be)
    out = pl.pallas_call(
        _interval_count_block,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_ROWS, be), lambda b, j, k: (b, k)),
            pl.BlockSpec((_ROWS, be), lambda b, j, k: (b, k)),
            pl.BlockSpec((_ROWS, be), lambda b, j, k: (b, k)),
            pl.BlockSpec((_ROWS, bp), lambda b, j, k: (b, j)),
        ],
        out_specs=pl.BlockSpec((_ROWS, bp), lambda b, j, k: (b, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Pp), jnp.int32),
        interpret=interpret,
        name="interval_count",
    )(lo2, hi2, sg2, pos2)
    return out[:B, :P]
