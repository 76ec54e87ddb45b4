"""Jit-cached dispatches for the resident merge-round device ops.

`ResidentBitmapArena` (core/resident.py) calls two functions per round:

* `topj_fn` — the fused ranking: all groups' (B, G, J) ranked top-J
  candidate columns from the RESIDENT bitmaps, then a device-side gather of
  the dirty rows, downloaded as (n, J) int8 — the only per-round score
  traffic.
* `fold_fn` — the bitset-OR fold: applies the round's accepted pairs to the
  resident bitmaps. Both positional buffers are donated, so the update is
  in place (the Pallas kernel additionally aliases input→output).

Dispatch picks the Pallas kernels on TPU and their integer-exact jnp twins
(`ref.py`) elsewhere (`kernels/common.default_use_kernel`); either path is
bit-identical (test-enforced). With a mesh, the batch axis is shard_map'd
over the data axes exactly like the PR-4 intersection dispatch. Compiled
executables live in small LRU caches keyed on padded shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import faults
from repro.kernels.bitset_fold import ref
from repro.kernels.bitset_fold.kernel import (bitset_fold_kernel,
                                              jaccard_topj_kernel)
from repro.kernels.common import LruCache, mesh_content_key, shard_map_no_check

_TOPJ_CACHE = LruCache(16)
_FOLD_CACHE = LruCache(16)
# one round, fold and extraction op per workspace chunk shape: an
# iteration at 2^18 nodes has ~50 chunk shapes, and later iterations
# repeat most of them
_ROUND_CACHE = LruCache(128)
_FOLDC_CACHE = LruCache(128)
_EXTRACT_CACHE = LruCache(128)


class _Dispatch:
    """One cached device op: its jitted function plus the fault-injection
    hook that runs before every dispatch.

    `compile` lowers and compiles the op for the exact arguments it is
    about to receive (once per cache entry; the jit call reuses the
    executable), so a kernel the chip's compiler refuses raises THERE —
    callers compile outside their dispatch retry, and only a fault of an
    already-compiled dispatch ever reaches the retry. The `faults.check`
    runs BEFORE the jit call, while the donated input buffers are still
    intact, so an injected dispatch fault is retry-safe (the arena retries
    once on the ref twin, DESIGN.md §11)."""

    def __init__(self, site: str, jitted):
        self.site = site
        self.jitted = jitted
        self._compiled = None

    def compile(self, *args):
        if self._compiled is None:
            self._compiled = self.jitted.lower(*args).compile()
        return self._compiled

    def __call__(self, *args):
        faults.check(self.site)
        return self.jitted(*args)


def _shard(fn, mesh, axes, n_in, n_out):
    spec = P(axes if len(axes) > 1 else axes[0])
    return shard_map_no_check(
        fn, mesh, (spec,) * n_in,
        (spec,) * n_out if n_out > 1 else spec)


def topj_fn(B: int, G: int, W: int, J: int, n_pad: int, *, use_kernel: bool,
            interpret: bool, mesh=None, axes=("data",)):
    """Compiled ``(bits (B,G,W) u32, alive (B,G) i32, rows (n_pad,2) i32)
    -> (n_pad, J) int8`` ranked-candidate gather, LRU-cached on shapes."""
    key = ("topj", B, G, W, J, n_pad, use_kernel, interpret, mesh_content_key(mesh))
    fn = _TOPJ_CACHE.get(key)
    if fn is not None:
        return fn

    if use_kernel or mesh is not None:
        # all-groups compute (vmap/shard-friendly), dirty rows gathered on
        # device so only (n, J) crosses the boundary
        if use_kernel:
            def all_topj(bits, alive):
                return jax.vmap(
                    lambda bb, aa: jaccard_topj_kernel(bb, aa[:, None], J,
                                                       interpret=interpret)
                )(bits, alive)
        else:
            all_topj = functools.partial(ref.topj_all, J=J)
        ranked = (_shard(all_topj, mesh, axes, 2, 1) if mesh is not None
                  else all_topj)

        @jax.jit
        def fn(bits, alive, rows):
            t = ranked(bits, alive)                # (B, G, J) int32
            return t[rows[:, 0], rows[:, 1]].astype(jnp.int8)
    else:
        # single-device jnp twin: compute the selected rows only — integer-
        # identical to the gather above, O(n·G·W) instead of O(B·G²·W)
        @jax.jit
        def fn(bits, alive, rows):
            return ref.topj_rows(bits, alive, rows, J).astype(jnp.int8)

    fn = _Dispatch("kernel.bitset_fold.topj", fn)
    _TOPJ_CACHE[key] = fn
    return fn


def fold_fn(B: int, G: int, W: int, P_pairs: int, *, use_kernel: bool,
            interpret: bool, mesh=None, axes=("data",)):
    """Compiled ``(bits, alive, instr (B,P,8) i32) -> (bits', alive')`` with
    bits/alive donated — the resident buffers fold in place."""
    key = ("fold", B, G, W, P_pairs, use_kernel, interpret, mesh_content_key(mesh))
    fn = _FOLD_CACHE.get(key)
    if fn is not None:
        return fn

    if use_kernel:
        def one(bits_g, alive_g, instr_g):
            b2, a2 = bitset_fold_kernel(bits_g, alive_g[:, None], instr_g,
                                        interpret=interpret)
            return b2, a2[:, 0]
    else:
        one = ref.fold_pairs
    v = jax.vmap(one)
    folded = _shard(v, mesh, axes, 3, 2) if mesh is not None else v

    def widened(bits, alive, instr):
        # instr crosses the wire as int16; index arithmetic wants int32
        return folded(bits, alive, instr.astype(jnp.int32))

    fn = _Dispatch("kernel.bitset_fold.fold",
                   jax.jit(widened, donate_argnums=(0, 1)))
    _FOLD_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# Whole-iteration residency round ops (DESIGN.md §9, ISSUE 7)
# ---------------------------------------------------------------------------
def round_fn(B: int, G: int, R: int, W: int, K: int, J: int, top_j: int, *,
             height_bound, use_kernel: bool, interpret: bool, mesh=None,
             axes=("data",)):
    """Compiled fused proposal round over the RESIDENT state.

    ``(bits, alive, dirty, CNT, colsize, memcol, s, selfc, nd, hgt, cost,
    theta_p) -> (dirty', out)``. The dirty-row list never crosses the
    boundary: the device derives it from its own ``dirty`` mirror
    (`jnp.nonzero` in row-major order — exactly the host's
    ``np.nonzero``), evaluates ranking + exact integer Saving + θ̂
    acceptance, and updates ``dirty`` in place (rows whose best Saving
    fails θ̂ leave the queue, matching the host sweep). Only ``out``
    (K, 2) int8 ``[accept, partner]`` comes back. ``theta_p`` is a traced
    uint32 scalar so θ stays out of the compiled shapes.

    Under a mesh the batch axis is sharded and `ref.round_all` evaluates
    every row (a sharded nonzero has no global order), so ``out`` is
    (B, G, 2) and the host gathers its dirty rows; decisions are
    identical. With ``use_kernel`` the Pallas `jaccard_topj` kernel owns
    the O(G²·W) ranking and `ref.round_from_ranked` the exact-Saving
    tail — the jnp path fuses both in `ref.round_rows`.
    """
    key = ("round", B, G, R, W, K, J, top_j, height_bound, use_kernel,
           interpret, mesh_content_key(mesh))
    fn = _ROUND_CACHE.get(key)
    if fn is not None:
        return fn

    if mesh is not None:
        def all_round(bits, alive, dirty, CNT, colsize, memcol, s, selfc,
                      nd, hgt, cost):
            return ref.round_all(bits, alive, dirty, CNT, colsize, memcol,
                                 s, selfc, nd, hgt, cost, J, top_j,
                                 height_bound)
        sharded = _shard(all_round, mesh, axes, 11, 1)

        @functools.partial(jax.jit, donate_argnums=(2,))
        def fn(bits, alive, dirty, CNT, colsize, memcol, s, selfc, nd, hgt,
               cost, theta_p):
            res = sharded(bits, alive, dirty, CNT, colsize, memcol, s,
                          selfc, nd, hgt, cost)                 # (B, G, 4)
            ok = (res[..., 0] > 0) & ref.theta_accept(
                res[..., 1], res[..., 2], theta_p)
            out = jnp.stack([ok.astype(jnp.int8),
                             res[..., 3].astype(jnp.int8)], axis=-1)
            # non-dirty rows had has=0 → ok=0, so a plain overwrite IS the
            # host rule "dirty rows stay dirty iff their proposal passed"
            return ok.astype(dirty.dtype), out
    else:
        @functools.partial(jax.jit, donate_argnums=(2,))
        def fn(bits, alive, dirty, CNT, colsize, memcol, s, selfc, nd, hgt,
               cost, theta_p):
            rb, rr = jnp.nonzero(dirty > 0, size=K, fill_value=(B, 0))
            rows = jnp.stack([rb.astype(jnp.int32),
                              rr.astype(jnp.int32)], axis=1)
            if use_kernel:
                cand_all = jax.vmap(
                    lambda bb, aa: jaccard_topj_kernel(bb, aa[:, None], J,
                                                       interpret=interpret)
                )(bits, alive)                                  # (B, G, J)
                cand = cand_all[jnp.minimum(rows[:, 0], B - 1), rows[:, 1]]
                res = ref.round_from_ranked(
                    alive, dirty, CNT, colsize, memcol, s, selfc, nd, hgt,
                    cost, rows, cand, top_j, height_bound)
            else:
                res = ref.round_rows(bits, alive, dirty, CNT, colsize,
                                     memcol, s, selfc, nd, hgt, cost, rows,
                                     J, top_j, height_bound)    # (K, 4)
            ok = (res[:, 0] > 0) & ref.theta_accept(res[:, 1], res[:, 2],
                                                    theta_p)
            out = jnp.stack([ok.astype(jnp.int8),
                             res[:, 3].astype(jnp.int8)], axis=-1)
            dirty = dirty.at[rows[:, 0], rows[:, 1]].set(
                ok.astype(dirty.dtype), mode="drop")
            return dirty, out

    fn = _Dispatch("kernel.bitset_fold.round", fn)
    _ROUND_CACHE[key] = fn
    return fn


def extract_fn(Bp: int, G: int, Rp: int, Wp: int, Lp: int, cap: int,
               E: int):
    """Compiled bank→arena extraction (ISSUE 9, DESIGN.md §9).

    ``(gids (E,), cnts (E,), size (cap,), selfc, nd, hgt, res_map (cap,),
    members (Bp,G) i32, ptr (Bp,G) i32, lens (Bp,G) i32) -> 11-tuple`` of
    a fresh chunk's resident state: bits (Bp,G,Wp) u32, alive/dirty i8,
    CNT (Bp,G,Rp) i32, colsize (Bp,Rp) i32, memcol/s/selfc/nd/hgt/cost
    (Bp,G) i32 — the exact shapes/dtypes `ResidentBitmapArena` uploads on
    the host-rebuilt path. The bank arrays are read WITHOUT donation, so
    concurrent chunk thunks may extract from the same bank.
    """
    key = ("extract", Bp, G, Rp, Wp, Lp, cap, E)
    fn = _EXTRACT_CACHE.get(key)
    if fn is not None:
        return fn

    per_b = functools.partial(ref.bank_extract_group, Rp=Rp, Wp=Wp, Lp=Lp)

    @jax.jit
    def fn(gids, cnts, size, selfc, nd, hgt, res_map, members, ptr, lens):
        return jax.vmap(per_b,
                        in_axes=(None, None, None, None, None, None, None,
                                 0, 0, 0))(gids, cnts, size, selfc, nd,
                                           hgt, res_map, members, ptr, lens)

    fn = _Dispatch("kernel.bitset_fold.extract", fn)
    _EXTRACT_CACHE[key] = fn
    return fn


def fold_counts_fn(B: int, G: int, R: int, W: int, P_pairs: int, *,
                   use_kernel: bool, interpret: bool, mesh=None,
                   axes=("data",)):
    """Compiled count-carrying fold: ``(bits, alive, dirty, CNT, colsize,
    memcol, s, selfc, nd, hgt, cost, instr (B,P,3) i32) -> 10-tuple`` of
    updated state (everything but ``memcol``, which merges never change).
    All state buffers are donated — the resident iteration state folds in
    place. With ``use_kernel`` the bitmap phase runs in the Pallas
    `bitset_fold` kernel (instruction word/bit fields derived on device
    from the resident ``memcol``) and the count phases in the jnp ref;
    the phases share no reads, so the split is exact.
    """
    key = ("foldc", B, G, R, W, P_pairs, use_kernel, interpret,
           mesh_content_key(mesh))
    fn = _FOLDC_CACHE.get(key)
    if fn is not None:
        return fn

    if use_kernel:
        def one(bits, alive, dirty, CNT, colsize, memcol, s, selfc, nd,
                hgt, cost, instr):
            out = ref.fold_pairs_counts(bits, alive, dirty, CNT, colsize,
                                        memcol, s, selfc, nd, hgt, cost,
                                        instr, with_bits=False)
            valid = instr[:, 2] > 0
            ag = jnp.minimum(jnp.where(valid, instr[:, 0], 0), G - 1)
            zg = jnp.minimum(jnp.where(valid, instr[:, 1], 0), G - 1)
            ca = memcol[ag]
            cz = memcol[zg]
            instr8 = jnp.stack(
                [ag, zg, ca >> 5, ca & 31, cz >> 5, cz & 31,
                 instr[:, 2], jnp.zeros_like(ca)], axis=1).astype(jnp.int32)
            nb, _ = bitset_fold_kernel(bits, alive[:, None], instr8,
                                       interpret=interpret)
            return (nb,) + tuple(out[1:])
    else:
        one = ref.fold_pairs_counts
    v = jax.vmap(one)
    folded = _shard(v, mesh, axes, 12, 10) if mesh is not None else v
    fn = _Dispatch("kernel.bitset_fold.fold_counts",
                   jax.jit(folded, donate_argnums=(0, 1, 2, 3, 4, 6, 7, 8,
                                                   9, 10)))
    _FOLDC_CACHE[key] = fn
    return fn
