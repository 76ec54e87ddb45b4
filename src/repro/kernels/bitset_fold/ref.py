"""jnp twins for the resident merge-round kernels (bitset_fold).

Everything here is INTEGER-EXACT and must stay in bit-for-bit lockstep with
three other implementations: the Pallas kernels in `kernel.py`, the NumPy
host ranking in `core/merging.py` (`rank_keys` / the per-round argsort), and
the host bitmap fold in `BatchedGroupWorkspace.apply_merges`. The merge
engine's cross-backend bit-identity rests on that agreement (DESIGN.md §9),
so these functions avoid floating point entirely:

* ``rank_keys`` — the quantized-Jaccard ranking key: shift intersection and
  union down together until the union fits 15 bits, then take the exact
  integer quotient ``(iq << 15) // uq``. Pure int32-safe arithmetic, so the
  key is identical on NumPy, XLA CPU, and TPU (no float division whose
  rounding could differ across backends).
* ``topj_all`` — per-row ranked top-J candidate columns by (key desc,
  column asc), dead/self columns last; J iterative argmax passes over a
  combined key that encodes the column tie-break, so there are never ties.
* ``fold_pairs`` — the bitset-OR merge fold: per accepted pair, fold column
  cz into ca for every row, OR row z into row a, clear z, clear a's own
  bit. Sequential over the (disjoint) pairs of a group, exactly like the
  kernel's fori_loop.
* the ISSUE-7 on-device Saving layer: 32-bit-limb wide multiply/compare
  (`umul32_wide` / `prod_lt`) so the rational Saving argmax and the
  quantized-θ acceptance are EXACT in int32/uint32 arithmetic (x64 stays
  disabled on device), the clamped integer pair costs (`poss_pair_c` /
  `poss_self_c` / `pair_cost_c`, mirrored by `core/merging.py` in int64),
  and the fused per-round proposal evaluation (`round_all` / `round_rows`)
  plus the count-carrying fold (`fold_pairs_counts`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_KEY_BITS = 15

# Integer-exact Saving contract (DESIGN.md §9). All backends clamp the
# "possible pairs" terms at C_CLAMP with the SAME expression, so decisions
# agree bit-for-bit even at the clamp; the host workspace build guards that
# real costs stay far below the clamp (exactness, not just agreement).
C_CLAMP = 1 << 30
# θ is quantized to θ̂ = P/2^20 with P = clip(ceil(θ·2^20), 0, 2^20): the
# acceptance test becomes the integer inequality (d−n)·2^20 ≥ P·d, identical
# on host int64 and device uint32 limbs. θ = 0 → P = 0 accepts Saving ≥ 0.
THETA_SHIFT = 20

def popcount_u32(x):
    return jnp.bitwise_count(x).astype(jnp.int32)


def bit_length(v):
    """Elementwise bit length of non-negative int32/int64 (< 2^31) values —
    the 5-step binary search is identical in NumPy and jnp."""
    b = jnp.zeros_like(v)
    for s in (16, 8, 4, 2, 1):
        t = v >> s
        big = t > 0
        b = b + jnp.where(big, s, 0)
        v = jnp.where(big, t, v)
    return b + (v > 0).astype(v.dtype)


def rank_keys(inter, deg_r, deg_c):
    """Quantized-Jaccard integer ranking keys (DESIGN.md §9).

    ``inter`` intersection counts, ``deg_r``/``deg_c`` the two rows' set
    sizes (broadcastable). Returns keys in ``[0, 2^15]`` that order exactly
    like ``inter/union`` up to the 15-bit quantization, computed with shift
    and integer-divide only.
    """
    inter = inter.astype(jnp.int32)
    union = deg_r.astype(jnp.int32) + deg_c.astype(jnp.int32) - inter
    sh = jnp.maximum(0, bit_length(union) - _KEY_BITS)
    return ((inter >> sh) << _KEY_BITS) // jnp.maximum(union >> sh, 1)


def combined_key(keys, ok, col, G: int):
    """Strict total order encoding: ``(key+1)*G - 1 - col`` for eligible
    columns, ``-1 - col`` for dead/self. Every entry is UNIQUE (the column
    is folded into both branches), so any top-k — `lax.top_k`, the kernel's
    iterative argmax, the host's stable argsort on ``-key`` — produces the
    SAME ranking: key desc, column asc, dead/self last (in asc column
    order, matching the stable sort over the host's uniform -1 keys)."""
    return jnp.where(ok, (keys + 1) * G - 1 - col, -1 - col)


def _topk_ranked(ckey, J: int):
    """Ranked top-J columns of the (…, G) combined keys; keys are unique,
    so top_k needs no tie rule."""
    _, idx = jax.lax.top_k(ckey, J)
    return idx.astype(jnp.int32)


def topj_all(bits, alive, J: int):
    """All rows' ranked top-J candidate columns, one group batch at a time.

    ``bits`` (B, G, W) uint32 packed neighbor bitmaps, ``alive`` (B, G)
    int8/int32/bool. Returns (B, G, J) int32 column indices, ranked by the
    exact (quantized key desc, column asc) order with dead/self columns
    last — the device analogue of the host sweep's per-row stable argsort
    prefix.
    """
    B, G, W = bits.shape
    inter = popcount_u32(bits[:, :, None, :] & bits[:, None, :, :]).sum(
        axis=-1).astype(jnp.int32)                      # (B, G, G)
    deg = jnp.diagonal(inter, axis1=1, axis2=2)         # popcount(x&x) = |x|
    keys = rank_keys(inter, deg[:, :, None], deg[:, None, :])
    col = jax.lax.broadcasted_iota(jnp.int32, (B, G, G), 2)
    row = jax.lax.broadcasted_iota(jnp.int32, (B, G, G), 1)
    ok = (alive[:, None, :] > 0) & (col != row)
    return _topk_ranked(combined_key(keys, ok, col, G), J)


def topj_rows(bits, alive, rows, J: int):
    """Ranked top-J for SELECTED rows only — the single-device fast path.

    ``rows`` (n, 2) int32 [group, row] pairs (padded rows compute garbage
    the caller discards). Integer-identical to gathering those rows out of
    `topj_all`; computing (n, G) instead of (B, G, G) intersections is what
    makes late merge rounds (few dirty rows) cheap.
    """
    B, G, W = bits.shape
    rb, rr = rows[:, 0], rows[:, 1]
    rowbits = bits[rb, rr]                                   # (n, W)
    inter = popcount_u32(rowbits[:, None, :] & bits[rb]).sum(
        axis=-1).astype(jnp.int32)                           # (n, G)
    deg = popcount_u32(bits).sum(axis=-1).astype(jnp.int32)  # (B, G)
    keys = rank_keys(inter, deg[rb, rr][:, None], deg[rb])
    col = jax.lax.broadcasted_iota(jnp.int32, inter.shape, 1)
    ok = (alive[rb] > 0) & (col != rr[:, None])
    return _topk_ranked(combined_key(keys, ok, col, G), J)


def fold_pairs(bits, alive, instr):
    """Apply one round's accepted merges to one group's resident bitmaps.

    ``bits`` (G, W) uint32, ``alive`` (G,) int32, ``instr`` (P, 8) int32
    rows ``[a_row, z_row, wa, ba, wz, bz, valid, _]`` (word/bit positions of
    the a/z member columns in the uint32 layout; ``valid`` gates padding
    rows). Pairs apply sequentially — their rows are disjoint, but two
    pairs' member columns may share a 32-bit word, so the word updates must
    be read-modify-write in order, exactly as the kernel's fori_loop and
    the host fold's unbuffered ``.at`` ops.
    """
    one = jnp.uint32(1)

    def body(p, carry):
        b, a = carry
        row = instr[p]
        valid = row[6] > 0
        ar, zr, wa, wz = row[0], row[1], row[2], row[4]
        ba = row[3].astype(jnp.uint32)
        bz = row[5].astype(jnp.uint32)
        colz = (b[:, wz] >> bz) & one
        nb = b.at[:, wa].set(b[:, wa] | (colz << ba))
        nb = nb.at[:, wz].set(nb[:, wz] & ~(one << bz))
        rowz = nb[zr]
        nb = nb.at[ar].set(nb[ar] | rowz)
        nb = nb.at[zr].set(jnp.zeros_like(rowz))
        nb = nb.at[ar, wa].set(nb[ar, wa] & ~(one << ba))
        na = a.at[zr].set(0)
        return jnp.where(valid, nb, b), jnp.where(valid, na, a)

    return jax.lax.fori_loop(0, instr.shape[0], body, (bits, alive))


# ---------------------------------------------------------------------------
# 32-bit-limb exact arithmetic (device x64 is disabled; int64 is unavailable)
# ---------------------------------------------------------------------------
def umul32_wide(x, y):
    """Exact 64-bit product of two non-negative int32/uint32 values as
    (hi, lo) uint32 limbs, via 16-bit half-word partial products."""
    x = x.astype(jnp.uint32)
    y = y.astype(jnp.uint32)
    m = jnp.uint32(0xFFFF)
    xl, xh = x & m, x >> jnp.uint32(16)
    yl, yh = y & m, y >> jnp.uint32(16)
    ll = xl * yl
    lh = xl * yh
    hl = xh * yl
    mid = (ll >> jnp.uint32(16)) + (lh & m) + (hl & m)   # < 3·2^16, no wrap
    lo = (mid << jnp.uint32(16)) | (ll & m)
    hi = xh * yh + (lh >> jnp.uint32(16)) + (hl >> jnp.uint32(16)) + (
        mid >> jnp.uint32(16))
    return hi, lo


def wide_gt(h1, l1, h2, l2):
    return (h1 > h2) | ((h1 == h2) & (l1 > l2))


def prod_lt(a, b, c, d):
    """a·b < c·d, exact, for non-negative int32 operands (via limbs)."""
    h1, l1 = umul32_wide(a, b)
    h2, l2 = umul32_wide(c, d)
    return wide_gt(h2, l2, h1, l1)


def theta_accept(numer, denom, theta_p):
    """Saving ≥ θ̂ as an exact integer test: denom > 0, numer ≤ denom and
    (denom − numer)·2^20 ≥ theta_p·denom. ``theta_p`` is a traced uint32
    scalar (P = clip(ceil(θ·2^20), 0, 2^20)); host twin in int64 is
    `core/merging.theta_accept_host`."""
    ok = (denom > 0) & (numer <= denom)
    diff = jnp.maximum(denom - numer, 0)
    h1, l1 = umul32_wide(diff, jnp.uint32(1 << THETA_SHIFT))
    h2, l2 = umul32_wide(jnp.broadcast_to(theta_p, diff.shape), denom)
    ge = ~wide_gt(h2, l2, h1, l1)
    return ok & ge


# ---------------------------------------------------------------------------
# Clamped integer pair costs (identical expressions on host int64)
# ---------------------------------------------------------------------------
def poss_pair_c(s_m, colsize):
    """min(s_m·colsize, C_CLAMP) without int32 overflow: the div-guarded
    `where` is exactly the clamped product for non-negative operands."""
    C = jnp.int32(C_CLAMP)
    big = s_m > C // jnp.maximum(colsize, 1)
    return jnp.where(big, C, s_m * colsize)


def poss_self_c(s):
    """min(s·(s−1)/2, C_CLAMP) without overflow (divide the even factor
    by 2 before multiplying; clamp above s = 46341)."""
    C = jnp.int32(C_CLAMP)
    half = jnp.where(s % 2 == 0, (s >> 1) * (s - 1), s * ((s - 1) >> 1))
    return jnp.where(s > 46341, C, jnp.minimum(half, C))


def pair_cost_c(cnt, poss_c):
    """min(cnt, poss − cnt + 1) on the clamped poss — 0 at cnt == 0."""
    return jnp.minimum(cnt, poss_c - cnt + 1)


# ---------------------------------------------------------------------------
# Fused per-round proposal evaluation (rank + exact Saving + argmax)
# ---------------------------------------------------------------------------
def _row_saving_terms(cnt_r, cnt_c, colsize_r, ca, cz, s_r, s_c, selfc_r,
                      selfc_c, nd_r, nd_c, cost_r, cost_c):
    """(numer, denom) of merging each row r with one candidate c — all int32,
    elementwise over the leading axis; the int64 host twin is
    `BatchedGroupWorkspace.saving_terms_rows`."""
    ri = jnp.arange(cnt_r.shape[0])
    merged = cnt_r + cnt_c
    s_m = s_r + s_c
    poss = poss_pair_c(s_m[:, None], colsize_r)
    cost_cols = pair_cost_c(merged, poss)
    total = cost_cols.sum(axis=-1) - cost_cols[ri, ca] - cost_cols[ri, cz]
    cab = cnt_r[ri, cz]
    self_m = selfc_r + selfc_c + cab
    total = total + pair_cost_c(self_m, poss_self_c(s_m))
    numer = total + nd_r + nd_c + jnp.int32(2)
    pair_c = pair_cost_c(cab, poss_pair_c(s_r, s_c))
    denom = cost_r + cost_c - pair_c
    return numer, denom


def round_all(bits, alive, dirty, CNT, colsize, memcol, s, selfc, nd, hgt,
              cost, J: int, top_j: int, height_bound):
    """Best-candidate proposal of EVERY row of one batch: (B, G, 4) int32
    ``[has, numer, denom, z]``.

    Streams the ranked candidates one at a time (J argmax passes over the
    combined keys — identical ranking to `topj_all`), evaluating the exact
    integer Saving terms per candidate and keeping the best by the exact
    rational comparison ``n_j·d_best < n_best·d_j`` (strict, so ranked ties
    keep the earlier candidate — the host sweep's first-max rule). θ is NOT
    applied here: the caller tests `theta_accept` on (numer, denom), which
    keeps θ out of the compiled shapes. ``dirty`` only masks ``has`` so
    clean rows never propose.
    """
    B, G, W = bits.shape
    R = CNT.shape[-1]
    inter = popcount_u32(bits[:, :, None, :] & bits[:, None, :, :]).sum(
        axis=-1).astype(jnp.int32)                       # (B, G, G)
    deg = jnp.diagonal(inter, axis1=1, axis2=2)
    keys = rank_keys(inter, deg[:, :, None], deg[:, None, :])
    col = jax.lax.broadcasted_iota(jnp.int32, (B, G, G), 2)
    row = jax.lax.broadcasted_iota(jnp.int32, (B, G, G), 1)
    okc = (alive[:, None, :] > 0) & (col != row)
    ckey = combined_key(keys, okc, col, G)
    alive_cnt = (alive > 0).astype(jnp.int32).sum(axis=1)          # (B,)
    j_row = jnp.minimum(jnp.int32(top_j), alive_cnt - 1)[:, None]  # (B, 1)
    bi = jnp.arange(B)[:, None]
    colsize_b = jnp.broadcast_to(colsize[:, None, :], (B, G, R))

    def body(j, carry):
        ckey, has, n_b, d_b, z_b = carry
        idx = jnp.argmax(ckey, axis=2).astype(jnp.int32)           # (B, G)
        kmax = jnp.take_along_axis(ckey, idx[:, :, None], axis=2)[..., 0]
        numer, denom = jax.vmap(_row_saving_terms)(
            CNT, CNT[bi, idx], colsize_b, memcol, memcol[bi, idx],
            s, s[bi, idx], selfc, selfc[bi, idx], nd, nd[bi, idx],
            cost, cost[bi, idx])
        valid = (kmax >= 0) & (j < j_row) & (denom > 0)
        if height_bound is not None:
            new_h = jnp.maximum(hgt, hgt[bi, idx]) + 1
            valid = valid & (new_h <= jnp.int32(height_bound))
        take = valid & (~has | prod_lt(numer, d_b, n_b, denom))
        n_b = jnp.where(take, numer, n_b)
        d_b = jnp.where(take, denom, d_b)
        z_b = jnp.where(take, idx, z_b)
        has = has | take
        ckey = jnp.where(col == idx[:, :, None], jnp.int32(-(2**31) + 1),
                         ckey)
        return ckey, has, n_b, d_b, z_b

    one0 = jnp.ones((B, G), dtype=jnp.int32)
    _, has, n_b, d_b, z_b = jax.lax.fori_loop(
        0, J, body,
        (ckey, jnp.zeros((B, G), dtype=bool), one0, one0,
         jnp.zeros((B, G), dtype=jnp.int32)))
    has = has & (dirty > 0) & (alive > 0)
    return jnp.stack([has.astype(jnp.int32), n_b, d_b, z_b], axis=-1)


def round_rows(bits, alive, dirty, CNT, colsize, memcol, s, selfc, nd, hgt,
               cost, rows, J: int, top_j: int, height_bound):
    """`round_all` restricted to the selected rows — the single-device fast
    path: O(K·G·(W+R)) per round instead of O(B·G²·(W+R)).

    ``rows`` (K, 2) int32 [group, row]; padding rows carry group index B
    (out of range: gathers clip, and the caller's scatters drop them).
    Returns (K, 4) int32 ``[has, numer, denom, z]``, integer-identical to
    gathering those rows out of `round_all`.
    """
    B, G, W = bits.shape
    R = CNT.shape[-1]
    rb = jnp.minimum(rows[:, 0], B - 1)
    rr = rows[:, 1]
    pad_ok = rows[:, 0] < B
    K = rb.shape[0]
    rowbits = bits[rb, rr]                                         # (K, W)
    inter = popcount_u32(rowbits[:, None, :] & bits[rb]).sum(
        axis=-1).astype(jnp.int32)                                 # (K, G)
    deg = popcount_u32(bits).sum(axis=-1).astype(jnp.int32)        # (B, G)
    keys = rank_keys(inter, deg[rb, rr][:, None], deg[rb])
    col = jax.lax.broadcasted_iota(jnp.int32, (K, G), 1)
    okc = (alive[rb] > 0) & (col != rr[:, None])
    ckey = combined_key(keys, okc, col, G)
    alive_cnt = (alive > 0).astype(jnp.int32).sum(axis=1)
    j_row = jnp.minimum(jnp.int32(top_j), alive_cnt[rb] - 1)       # (K,)
    ki = jnp.arange(K)
    cnt_r = CNT[rb, rr]                                            # (K, R)
    colsize_r = colsize[rb]                                        # (K, R)
    ca = memcol[rb, rr]
    s_r, selfc_r = s[rb, rr], selfc[rb, rr]
    nd_r, hgt_r, cost_r = nd[rb, rr], hgt[rb, rr], cost[rb, rr]

    def body(j, carry):
        ckey, has, n_b, d_b, z_b = carry
        idx = jnp.argmax(ckey, axis=1).astype(jnp.int32)           # (K,)
        kmax = ckey[ki, idx]
        numer, denom = _row_saving_terms(
            cnt_r, CNT[rb, idx], colsize_r, ca, memcol[rb, idx], s_r,
            s[rb, idx], selfc_r, selfc[rb, idx], nd_r, nd[rb, idx], cost_r,
            cost[rb, idx])
        valid = (kmax >= 0) & (j < j_row) & (denom > 0)
        if height_bound is not None:
            new_h = jnp.maximum(hgt_r, hgt[rb, idx]) + 1
            valid = valid & (new_h <= jnp.int32(height_bound))
        take = valid & (~has | prod_lt(numer, d_b, n_b, denom))
        n_b = jnp.where(take, numer, n_b)
        d_b = jnp.where(take, denom, d_b)
        z_b = jnp.where(take, idx, z_b)
        has = has | take
        ckey = jnp.where(col == idx[:, None], jnp.int32(-(2**31) + 1), ckey)
        return ckey, has, n_b, d_b, z_b

    one0 = jnp.ones(K, dtype=jnp.int32)
    _, has, n_b, d_b, z_b = jax.lax.fori_loop(
        0, J, body,
        (ckey, jnp.zeros(K, dtype=bool), one0, one0,
         jnp.zeros(K, dtype=jnp.int32)))
    has = has & pad_ok & (dirty[rb, rr] > 0) & (alive[rb, rr] > 0)
    return jnp.stack([has.astype(jnp.int32), n_b, d_b, z_b], axis=-1)


def round_from_ranked(alive, dirty, CNT, colsize, memcol, s, selfc, nd, hgt,
                      cost, rows, cand, top_j: int, height_bound):
    """The Saving/argmax tail of `round_rows` over an EXTERNALLY ranked
    candidate list — the kernel-path hybrid: the Pallas `jaccard_topj`
    kernel produces ``cand`` (K, J) ranked columns (eligible candidates
    strictly precede dead/self ones in the combined-key order, so position
    j of the list IS the j-th eligible candidate while any remain), and
    this evaluates the identical exact first-wins rational argmax over it.
    Integer-identical to `round_rows` on the same state.
    """
    B, G = alive.shape
    rb = jnp.minimum(rows[:, 0], B - 1)
    rr = rows[:, 1]
    pad_ok = rows[:, 0] < B
    K, J = cand.shape
    alive_cnt = (alive > 0).astype(jnp.int32).sum(axis=1)
    j_row = jnp.minimum(jnp.int32(top_j), alive_cnt[rb] - 1)       # (K,)
    cnt_r = CNT[rb, rr]
    colsize_r = colsize[rb]
    ca = memcol[rb, rr]
    s_r, selfc_r = s[rb, rr], selfc[rb, rr]
    nd_r, hgt_r, cost_r = nd[rb, rr], hgt[rb, rr], cost[rb, rr]

    def body(j, carry):
        has, n_b, d_b, z_b = carry
        idx = cand[:, j]
        elig = (alive[rb, idx] > 0) & (idx != rr)
        numer, denom = _row_saving_terms(
            cnt_r, CNT[rb, idx], colsize_r, ca, memcol[rb, idx], s_r,
            s[rb, idx], selfc_r, selfc[rb, idx], nd_r, nd[rb, idx], cost_r,
            cost[rb, idx])
        valid = elig & (j < j_row) & (denom > 0)
        if height_bound is not None:
            new_h = jnp.maximum(hgt_r, hgt[rb, idx]) + 1
            valid = valid & (new_h <= jnp.int32(height_bound))
        take = valid & (~has | prod_lt(numer, d_b, n_b, denom))
        n_b = jnp.where(take, numer, n_b)
        d_b = jnp.where(take, denom, d_b)
        z_b = jnp.where(take, idx, z_b)
        return has | take, n_b, d_b, z_b

    one0 = jnp.ones(K, dtype=jnp.int32)
    has, n_b, d_b, z_b = jax.lax.fori_loop(
        0, J, body,
        (jnp.zeros(K, dtype=bool), one0, one0,
         jnp.zeros(K, dtype=jnp.int32)))
    has = has & pad_ok & (dirty[rb, rr] > 0) & (alive[rb, rr] > 0)
    return jnp.stack([has.astype(jnp.int32), n_b, d_b, z_b], axis=-1)


# ---------------------------------------------------------------------------
# Adjacency-bank carry (ISSUE 9): advance + extract twins
# ---------------------------------------------------------------------------
_INT32_INF = (1 << 31) - 1


def bank_advance(gids, cnts, size, selfc, nd, hgt, res_map, slab, Tp: int):
    """Advance the resident adjacency bank by ONE applied merge batch.

    ``gids``/``cnts`` are the (E,) append-only id/count streams, the four
    (cap,) stat arrays mirror `SluggerState`'s size/selfcnt/ndesc/height,
    ``res_map`` is the pre-batch root map, and ``slab`` is the (8, Pp) i32
    instruction ``[A, Z, M, out_ptr, a_ptr, a_len, z_ptr, z_len]`` (pads
    carry ``A = Z = M = cap``, ``out_ptr = E``, zero lengths — every pad
    write scatter-drops). ``Tp`` is the padded flattened entry count.

    The batch is the device twin of `SluggerState.merge_batch`'s row build:
    gather both parents' bank rows, resolve every gid through the PRE-batch
    ``res_map`` (exactly the host's `resolve` at gather time), drop entries
    internal to the pair (their count sum, halved, is ``cab``), coalesce
    duplicate roots (stable two-key sort + segment heads — the host's keyed
    `argsort` + `reduceat`), and append each pair's unique external
    ``(root, count)`` entries at ``out_ptr`` in ascending-root order. The
    head count per pair equals the host's ``row_len[M]`` at creation, which
    the caller mirrors into its host length table.
    """
    i32 = jnp.int32
    E = gids.shape[0]
    cap = res_map.shape[0]
    Pp = slab.shape[1]
    A, Z, M, outp = slab[0], slab[1], slab[2], slab[3]
    aptr, alen, zptr, zlen = slab[4], slab[5], slab[6], slab[7]
    ub = alen + zlen
    cum = jnp.cumsum(ub)
    total = cum[Pp - 1]
    j = jnp.arange(Tp, dtype=i32)
    p = jnp.searchsorted(cum, j, side="right").astype(i32)
    pc = jnp.minimum(p, Pp - 1)
    w = j - (cum[pc] - ub[pc])
    from_z = w >= alen[pc]
    idx = jnp.where(from_z, zptr[pc] + (w - alen[pc]), aptr[pc] + w)
    ev = j < total
    idxc = jnp.clip(idx, 0, E - 1)
    e_cnt = jnp.where(ev, cnts[idxc], 0)
    rg = res_map[jnp.clip(gids[idxc], 0, cap - 1)]
    internal = ev & ((rg == A[pc]) | (rg == Z[pc]))
    # A→B and B→A each counted once — the exact host `cab` halving
    cab = jax.ops.segment_sum(jnp.where(internal, e_cnt, 0), pc,
                              num_segments=Pp) // 2
    keep = ev & ~internal
    # stable sort by (pair, root): one composite i32 key would overflow, so
    # sort by root first, then stably by pair — kept entries of one pair end
    # up contiguous and ascending by root, dropped entries sink to the end
    o1 = jnp.argsort(jnp.where(keep, rg, _INT32_INF), stable=True)
    o2 = jnp.argsort(jnp.where(keep, pc, Pp)[o1], stable=True)
    o = o1[o2]
    sp, srg, skeep, sc = pc[o], rg[o], keep[o], e_cnt[o]
    prev_p = jnp.concatenate([jnp.full((1,), -1, i32), sp[:-1]])
    prev_r = jnp.concatenate([jnp.full((1,), -1, i32), srg[:-1]])
    head = skeep & ((sp != prev_p) | (srg != prev_r))
    rank = jnp.cumsum(head.astype(i32)) - 1          # unique-entry index
    rankc = jnp.clip(rank, 0, Tp - 1)
    csum = jax.ops.segment_sum(jnp.where(skeep, sc, 0), rankc,
                               num_segments=Tp)      # coalesced counts
    base = jax.ops.segment_min(jnp.where(skeep, rank, Tp),
                               jnp.where(skeep, sp, Pp),
                               num_segments=Pp + 1)[:Pp]
    tgt = jnp.where(head, outp[sp] + (rank - base[sp]), E)
    gids = gids.at[tgt].set(srg, mode="drop")
    cnts = cnts.at[tgt].set(csum[rankc], mode="drop")
    # per-id stats of the minted parents (pads gather id 0, scatter-drop)
    Ac = jnp.clip(A, 0, cap - 1)
    Zc = jnp.clip(Z, 0, cap - 1)
    size = size.at[M].set(size[Ac] + size[Zc], mode="drop")
    selfc = selfc.at[M].set(selfc[Ac] + selfc[Zc] + cab, mode="drop")
    nd = nd.at[M].set(nd[Ac] + nd[Zc] + 2, mode="drop")
    hgt = hgt.at[M].set(jnp.maximum(hgt[Ac], hgt[Zc]) + 1, mode="drop")
    # ids rooted at A or Z now root at M (single composition step — A and Z
    # were roots before this batch, so no pointer chasing is needed)
    upd = jnp.arange(cap, dtype=i32)
    upd = upd.at[A].set(M, mode="drop").at[Z].set(M, mode="drop")
    return gids, cnts, size, selfc, nd, hgt, upd[res_map]


def bank_extract_group(gids, cnts, size, selfc, nd, hgt, res_map, members,
                       ptr, lens, Rp: int, Wp: int, Lp: int):
    """Build ONE group's resident-arena tensors straight from the bank.

    ``members``/``ptr``/``lens`` are the group's (G,) member roots (pad −1)
    and their bank row extents. The column universe is the sorted union of
    the members and their entries' CURRENT roots (``res_map`` resolution =
    the host's `resolve` at gather time); duplicate-root entries coalesce by
    scatter-add, exactly like the host's keyed unique — so CNT/colsize/
    memcol/bits come out bit-identical to a host `_fill` of the same chunk.
    Cost rows evaluate the clamped integer-Saving terms in int32; the bank
    init guard (Σcnt conservation) keeps every count and cost below C_CLAMP,
    so no device-side overflow check is needed.
    """
    i32 = jnp.int32
    INF = jnp.int32(_INT32_INF)
    G = members.shape[0]
    E = gids.shape[0]
    cap = res_map.shape[0]
    valid_mem = members >= 0
    mem_c = jnp.clip(members, 0, cap - 1)
    cum = jnp.cumsum(lens)
    total = cum[G - 1]
    j = jnp.arange(Lp, dtype=i32)
    r = jnp.searchsorted(cum, j, side="right").astype(i32)
    rc = jnp.minimum(r, G - 1)
    idx = ptr[rc] + (j - (cum[rc] - lens[rc]))
    ev = j < total
    idxc = jnp.clip(idx, 0, E - 1)
    e_cnt = jnp.where(ev, cnts[idxc], 0)
    e_root = res_map[jnp.clip(gids[idxc], 0, cap - 1)]
    # sorted column universe (members always own a column; INF pads last)
    U = jnp.sort(jnp.concatenate([jnp.where(valid_mem, members, INF),
                                  jnp.where(ev, e_root, INF)]))
    prev = jnp.concatenate([jnp.full((1,), -1, i32), U[:-1]])
    head = (U != prev) & (U != INF)
    rankU = jnp.cumsum(head.astype(i32)) - 1
    colgid = jnp.full((Rp,), INF, i32).at[
        jnp.where(head, rankU, Rp)].set(U, mode="drop")
    memcol = jnp.where(valid_mem,
                       jnp.searchsorted(colgid, mem_c).astype(i32), 0)
    ec = jnp.minimum(jnp.searchsorted(colgid, e_root).astype(i32), Rp - 1)
    CNT = jnp.zeros((G, Rp), i32).at[rc, ec].add(e_cnt)
    colsize = jnp.where(colgid != INF, size[jnp.clip(colgid, 0, cap - 1)], 0)
    s_g = jnp.where(valid_mem, size[mem_c], 0)
    selfc_g = jnp.where(valid_mem, selfc[mem_c], 0)
    nd_g = jnp.where(valid_mem, nd[mem_c], 0)
    hgt_g = jnp.where(valid_mem, hgt[mem_c], 0)
    # packed bitmaps: presence of column c lands in u32 word c>>5 bit c&31 —
    # the uint32 view of the host's little-endian uint64 layout
    pres = jnp.zeros((G, Wp * 32), dtype=jnp.uint32).at[:, :Rp].set(
        (CNT > 0).astype(jnp.uint32))
    bits = (pres.reshape(G, Wp, 32)
            << jnp.arange(32, dtype=jnp.uint32)).sum(
                axis=-1, dtype=jnp.uint32)
    terms = pair_cost_c(CNT, poss_pair_c(s_g[:, None], colsize[None, :]))
    cost = terms.sum(axis=-1, dtype=i32)
    cost = cost + pair_cost_c(selfc_g, poss_self_c(s_g)) + nd_g
    cost = jnp.where(valid_mem, cost, 0)
    alive = valid_mem.astype(jnp.int8)
    return (bits, alive, alive, CNT, colsize, memcol, s_g, selfc_g, nd_g,
            hgt_g, cost)


# ---------------------------------------------------------------------------
# Fold with resident counts (the whole-iteration residency fold)
# ---------------------------------------------------------------------------
def fold_pairs_counts(bits, alive, dirty, CNT, colsize, memcol, s, selfc,
                      nd, hgt, cost, instr, with_bits: bool = True):
    """Apply one round's accepted pairs to ONE group's resident tensors.

    ``instr`` (P, 3) int32 rows ``[a_row, z_row, valid]``; member columns
    come from the resident ``memcol``. The update is PHASED exactly like the
    host fold (`BatchedGroupWorkspace.apply_merges`): capture pre-round
    costs/cab for every pair, fold all CNT rows then all CNT columns, fold
    bitmap columns (all ORs, then all clears) then rows, update the scalar
    per-row stats, and finally apply the incremental + exact cost updates.
    Within one round pairs are disjoint in rows and member columns, so every
    phase's scatters hit distinct targets (word-level bit scatters combine
    distinct bits and are built as masks before a single OR/ANDNOT).

    ``with_bits=False`` skips the bitmap phase (bits pass through
    unchanged) — the kernel-path hybrid folds the bitmaps with the Pallas
    `bitset_fold` kernel and only the count phases run here; no count
    phase reads ``bits``, so the split changes nothing.
    """
    G, R = CNT.shape
    W = bits.shape[1]
    P = instr.shape[0]
    valid = instr[:, 2] > 0
    # drop-mode indices: invalid pairs scatter out of range / gather row 0
    a = jnp.where(valid, instr[:, 0], G)
    z = jnp.where(valid, instr[:, 1], G)
    ag = jnp.minimum(a, G - 1)
    zg = jnp.minimum(z, G - 1)
    ca = jnp.where(valid, memcol[ag], R)
    cz = jnp.where(valid, memcol[zg], R)
    cag = jnp.minimum(ca, R - 1)
    czg = jnp.minimum(cz, R - 1)
    vz32 = valid.astype(jnp.int32)

    # -- phase 0: pre-round captures ------------------------------------
    s_new = s[ag] + s[zg]
    cab = CNT[ag, czg] * vz32
    old_ca = pair_cost_c(CNT[:, cag], poss_pair_c(s[:, None], colsize[cag][None, :])).T   # (P, G)
    old_cz = pair_cost_c(CNT[:, czg], poss_pair_c(s[:, None], colsize[czg][None, :])).T   # (P, G)

    # -- phase 1: CNT rows fold, then columns fold ----------------------
    zrows = CNT[zg] * vz32[:, None]
    CNT = CNT.at[a].add(zrows, mode="drop")
    CNT = CNT.at[z].set(0, mode="drop")
    zcols = CNT[:, czg] * vz32[None, :]
    CNT = CNT.at[:, ca].add(zcols, mode="drop")
    CNT = CNT.at[:, cz].set(0, mode="drop")
    CNT = CNT.at[a, ca].set(0, mode="drop")

    # -- phase 2: bitmaps (column ORs, column clears, row ORs) ----------
    if with_bits:
        one = jnp.uint32(1)
        wa, ba = cag >> 5, (cag & 31).astype(jnp.uint32)
        wz, bz = czg >> 5, (czg & 31).astype(jnp.uint32)
        zbit = ((bits[:, wz] >> bz[None, :]) & one) * vz32.astype(jnp.uint32)
        # distinct pairs own distinct columns → distinct (word, bit)
        # targets: scatter-ADD builds the OR/clear masks without carries
        ormask = jnp.zeros_like(bits).at[:, wa].add(zbit << ba[None, :])
        clrmask = jnp.zeros_like(bits).at[:, wz].add(
            jnp.broadcast_to((one << bz) * vz32.astype(jnp.uint32), (G, P)))
        bits = (bits | ormask) & ~clrmask
        rowz = bits[zg] * vz32[:, None].astype(jnp.uint32)
        bits = bits.at[a].set((bits[ag] | rowz) * valid[:, None] +
                              bits[ag] * (~valid[:, None]), mode="drop")
        bits = bits.at[z].set(0, mode="drop")
        ownmask = jnp.zeros_like(bits).at[a, wa].add(
            (one << ba) * valid.astype(jnp.uint32), mode="drop")
        bits = bits & ~ownmask

    # -- phase 3: per-row scalar stats ----------------------------------
    colsize = colsize.at[ca].set(s_new, mode="drop")
    colsize = colsize.at[cz].set(0, mode="drop")
    selfc = selfc.at[a].set(selfc[ag] + selfc[zg] + cab, mode="drop")
    nd = nd.at[a].set(nd[ag] + nd[zg] + 2, mode="drop")
    hgt = hgt.at[a].set(jnp.maximum(hgt[ag], hgt[zg]) + 1, mode="drop")
    s = s.at[a].set(s_new, mode="drop")
    alive = alive.at[z].set(0, mode="drop")
    dirty = dirty.at[z].set(0, mode="drop")
    dirty = dirty.at[a].set(1, mode="drop")

    # -- phase 4: incremental cost update + exact merged-row recompute --
    new_ca = pair_cost_c(CNT[:, cag], poss_pair_c(s[:, None], colsize[cag][None, :])).T
    cost = cost + ((new_ca - old_ca - old_cz) * vz32[:, None]).sum(axis=0)
    crow = pair_cost_c(CNT[ag], poss_pair_c(s[ag][:, None], colsize[None, :])).sum(axis=-1)
    crow = crow + pair_cost_c(selfc[ag], poss_self_c(s[ag])) + nd[ag]
    cost = cost.at[a].set(crow, mode="drop")
    cost = cost.at[z].set(0, mode="drop")
    return bits, alive, dirty, CNT, colsize, s, selfc, nd, hgt, cost
