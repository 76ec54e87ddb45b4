"""Pallas TPU kernels for device-resident merge rounds (DESIGN.md §9).

Two kernels over one group's packed (G, W) uint32 neighbor bitmaps:

* `jaccard_topj_kernel` — the fused ranking step: streams the W axis
  through VMEM and accumulates the (G, G) pairwise intersection counts on
  the MXU (one bf16 0/1 matmul per bit plane, exact in its f32
  accumulator), then — on the last W block — turns them into quantized
  integer Jaccard keys and reduces to each row's ranked top-J candidate
  columns ON DEVICE. The host receives (G, J) instead of a (G, G) score
  matrix; the ranking order (key desc, column asc, dead/self last) is
  bit-identical to the host sweep's stable argsort (see `ref.py`).
* `bitset_fold_kernel` — the bitset-OR merge fold: applies one round's
  accepted pairs to the resident bitmaps in place (input/output aliased, so
  under jit donation nothing round-trips to host). Pairs are sequential in
  a fori_loop: their rows are disjoint, but member columns of different
  pairs may share a 32-bit word.

Both kernels work on the int32 view of the words (bit operations are the
same; Mosaic has no uint32→float cast and prefers signed vectors) and
never store a scalar into VMEM: every update is a masked vector select
over a row, a 128-lane column window, or the whole block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitset_fold import ref
from repro.kernels.common import default_interpret

_LANES = 128
_SPENT = -(2**31) + 1   # combined key of an already-selected column


def _i32(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _topj_block(alive_ref, bits_ref, out_ref, inter_ref, *, J: int):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        inter_ref[...] = jnp.zeros_like(inter_ref)

    a = bits_ref[...]  # (G, BW) int32 view of the packed words
    acc = None
    for b in range(32):
        plane = ((a >> b) & 1).astype(jnp.float32).astype(jnp.bfloat16)
        part = jax.lax.dot_general(plane, plane, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    # ≤ 32·BW ones per block: every partial sum is an exact f32 integer
    inter_ref[...] += acc.astype(jnp.int32)

    @pl.when(k == pl.num_programs(0) - 1)
    def _reduce():
        inter = inter_ref[...]
        G = inter.shape[0]
        col = jax.lax.broadcasted_iota(jnp.int32, (G, G), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (G, G), 0)
        diag = jnp.where(col == row, inter, 0)   # popcount(x & x) = |x|
        deg_r = diag.sum(axis=1, keepdims=True)
        deg_c = diag.sum(axis=0, keepdims=True)
        # the bit-identity-critical key arithmetic has ONE jnp home
        # (ref.rank_keys / ref.combined_key, pure elementwise, traceable
        # inside the kernel body); only top-k selection differs — unique
        # combined keys make iterative max here and lax.top_k in the jnp
        # twin rank identically with no tie rule anywhere
        key = ref.rank_keys(inter, deg_r, deg_c)
        ok = (alive_ref[...] > 0) & (col != row)
        ckey = ref.combined_key(key, ok, col, G)
        jcol = jax.lax.broadcasted_iota(jnp.int32, (G, J), 1)
        out = jnp.zeros((G, J), jnp.int32)
        for j in range(J):
            top = ckey.max(axis=1, keepdims=True)
            idx = jnp.where(ckey == top, col, G).min(axis=1, keepdims=True)
            out = jnp.where(jcol == j, idx, out)
            ckey = jnp.where(col == idx, _SPENT, ckey)
        out_ref[...] = out


def jaccard_topj_kernel(bits: jax.Array, alive: jax.Array, J: int,
                        block_w: int = 512, interpret=None) -> jax.Array:
    """bits (G, W) uint32, alive (G, 1) int8/int32 -> (G, J) int32 ranked
    candidate columns (quantized-Jaccard desc, column asc, dead/self last).

    W streams in ``block_w``-word blocks (a multiple of 128 on TPU); a
    narrower W is one full-width block, and a wider W that ``block_w`` does
    not divide is zero-padded — empty words change no intersection.
    """
    if interpret is None:
        interpret = default_interpret()
    G, W = bits.shape
    bw = W if W <= block_w else block_w
    Wp = pl.cdiv(W, bw) * bw
    words = _i32(bits)
    if Wp != W:
        words = jnp.pad(words, ((0, 0), (0, Wp - W)))
    alive_row = alive.reshape(1, G).astype(jnp.int32)
    return pl.pallas_call(
        functools.partial(_topj_block, J=J),
        grid=(Wp // bw,),
        in_specs=[
            pl.BlockSpec((1, G), lambda k: (0, 0)),
            pl.BlockSpec((G, bw), lambda k: (0, k)),
        ],
        out_specs=pl.BlockSpec((G, J), lambda k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((G, J), jnp.int32),
        scratch_shapes=[pltpu.VMEM((G, G), jnp.int32)],
        interpret=interpret,
    )(alive_row, words)


def _fold_block(instr_ref, bits_ref, alive_ref, obits_ref, oalive_ref, *,
                P: int):
    obits_ref[...] = bits_ref[...]
    oalive_ref[...] = alive_ref[...]
    G, W = obits_ref.shape
    # column ops touch one word of every row: work on the 128-lane window
    # holding it (a narrow or unaligned W is a single whole-width window)
    ww = _LANES if W % _LANES == 0 else W
    lane = jax.lax.broadcasted_iota(jnp.int32, (G, ww), 1)
    word = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (G, 1), 0)

    def window(w):
        if ww == W:
            return slice(None), w
        return pl.ds(pl.multiple_of((w // ww) * ww, ww), ww), w % ww

    def body(p, carry):
        @pl.when(instr_ref[p, 6] > 0)
        def _pair():
            ar, zr = instr_ref[p, 0], instr_ref[p, 1]
            wa, ba, bz = instr_ref[p, 2], instr_ref[p, 3], instr_ref[p, 5]
            sa, la = window(wa)
            sz, lz = window(instr_ref[p, 4])
            # fold member column cz into ca for every row …
            win = obits_ref[:, sz]
            colz = jnp.where(lane == lz, (win >> bz) & 1, 0).sum(
                axis=1, keepdims=True)                              # (G, 1)
            win = obits_ref[:, sa]
            obits_ref[:, sa] = jnp.where(lane == la, win | (colz << ba), win)
            win = obits_ref[:, sz]     # re-read: wa and wz may share a window
            obits_ref[:, sz] = jnp.where(lane == lz, win & ~(1 << bz), win)
            # … then OR row z into row a (dropping a's own bit) and retire z
            rowz = obits_ref[pl.ds(zr, 1), :]
            rowa = obits_ref[pl.ds(ar, 1), :] | rowz
            obits_ref[pl.ds(ar, 1), :] = jnp.where(word == wa,
                                                   rowa & ~(1 << ba), rowa)
            obits_ref[pl.ds(zr, 1), :] = jnp.zeros_like(rowz)
            oalive_ref[...] = jnp.where(rows == zr, 0, oalive_ref[...])
        return carry

    jax.lax.fori_loop(0, P, body, 0)


def bitset_fold_kernel(bits: jax.Array, alive: jax.Array, instr: jax.Array,
                       interpret=None):
    """Apply one round's merge pairs in place.

    bits (G, W) uint32, alive (G, 1) int8, instr (P, 8) int32 rows
    ``[a_row, z_row, wa, ba, wz, bz, valid, _]``. Returns (bits', alive'),
    aliased onto the inputs — with jit donation the resident buffers update
    without any host round-trip.
    """
    if interpret is None:
        interpret = default_interpret()
    G, W = bits.shape
    P = instr.shape[0]
    nb, na = pl.pallas_call(
        functools.partial(_fold_block, P=P),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((G, W), lambda: (0, 0)),
            pl.BlockSpec((G, 1), lambda: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((G, W), lambda: (0, 0)),
            pl.BlockSpec((G, 1), lambda: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, W), jnp.int32),
            jax.ShapeDtypeStruct((G, 1), jnp.int32),
        ],
        input_output_aliases={1: 0, 2: 1},
        interpret=interpret,
    )(instr.astype(jnp.int32), _i32(bits), alive.astype(jnp.int32))
    return (jax.lax.bitcast_convert_type(nb, jnp.uint32),
            na.astype(alive.dtype))
