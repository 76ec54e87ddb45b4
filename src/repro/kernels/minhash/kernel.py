"""Pallas TPU kernel: fused affine-hash + row-min (minhash shingles).

TPU adaptation (DESIGN.md §2.3): the CPU algorithm's irregular per-node
segment-min becomes a dense (R, W)-tiled reduction over fixed-width adjacency
rows — HBM-resident rows stream through VMEM in (BR, BW) blocks, each block
doing pure VPU work (uint32 multiply/xor/shift + min), with the W-dimension
reduced across grid steps into the (BR,) output block.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import default_interpret

_SENTINEL = np.uint32(0xFFFFFFFF)
_MAX_HASH = np.uint32(0xFFFFFFFF)


def _minhash_block(nbr_ref, out_ref, *, a: int, b: int, w_total: int):
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _MAX_HASH)

    x = nbr_ref[...]
    bw = x.shape[1]
    # mask block-padding columns past the true width (non-divisible shapes)
    col = w * bw + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = (x != _SENTINEL) & (col < w_total)
    h = x * np.uint32(a) + np.uint32(b)
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x7FEB352D)
    h = h ^ (h >> np.uint32(15))
    h = jnp.where(valid, h, _MAX_HASH)
    out_ref[...] = jnp.minimum(out_ref[...], jnp.min(h, axis=1))


def rowmin_hash_kernel(nbr: jax.Array, a: int, b: int,
                       block_r: int = 256, block_w: int = 128,
                       interpret=None) -> jax.Array:
    """(R, W) uint32 padded adjacency -> (R,) uint32 shingle values.

    Does not compile for TPU (its 1-D output block breaks the tiling
    rule); ``interpret`` defaults to interpreting off-TPU only, so a call
    on the chip fails instead of silently interpreting."""
    if interpret is None:
        interpret = default_interpret()
    R, W = nbr.shape
    br = min(block_r, R)
    bw = min(block_w, W)
    grid = (pl.cdiv(R, br), pl.cdiv(W, bw))
    return pl.pallas_call(
        functools.partial(_minhash_block, a=a, b=b, w_total=W),
        grid=grid,
        in_specs=[pl.BlockSpec((br, bw), lambda r, w: (r, w))],
        out_specs=pl.BlockSpec((br,), lambda r, w: (r,)),
        out_shape=jax.ShapeDtypeStruct((R,), jnp.uint32),
        interpret=interpret,
    )(nbr)
