"""Device ops for the resident run context (DESIGN.md §9, ISSUE 7).

These are the ops that make state OUTLIVE one iteration on device:

* `advance_fn` — plan replay: compose one iteration's applied merges
  ((A, Z, M) id triples) into the resident root map. The merges form a
  forest-forward map (an id merges at most once per iteration, and minted
  parents may merge again in LATER rounds of the same iteration), so the
  map collapses to its fixpoint by pointer doubling — 16 squarings cover
  chains of length 2^16, far beyond any real round count.
* `shingle_roots_fn` — resident candidate generation: per-root u32 min-hash
  shingles from the resident edge arrays and root map, plus per-root leaf
  counts (the host applies the leafless-root sentinel rule from the
  counts). Bit-identical to `core/minhash.node_shingles_u32` +
  `rootwise_min` and to the mesh shard_map path — same hash mix, and
  segment-min is order-independent.

Both are jit-cached on their (static) shapes via small LRU caches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import LruCache

from . import ref as _ref

_ADVANCE_CACHE = LruCache(8)
_SHINGLE_CACHE = LruCache(8)
_BANK_ADVANCE_CACHE = LruCache(16)
_BANK_GROW_CACHE = LruCache(8)


def _hash_u32(x, a, b):
    """The unified u32 mix (twin of `core/distributed._hash_u32` and the
    NumPy `core/minhash.hash_u32`)."""
    h = x.astype(jnp.uint32) * a + b
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> jnp.uint32(15))
    return h


def advance_fn(cap: int, mp: int):
    """Compiled ``(res_map (cap,) i32, tri (3, mp) i32) -> res_map'``.

    ``tri`` rows are the padded A / Z / M id streams (pads carry ``cap``,
    out of range — the scatters drop them). ``res_map`` is donated: the
    root map advances in place.
    """
    key = (cap, mp)
    fn = _ADVANCE_CACHE.get(key)
    if fn is not None:
        return fn

    @functools.partial(jax.jit, donate_argnums=(0,))
    def root_map_advance(res_map, tri):
        fwd = jnp.arange(cap, dtype=jnp.int32)
        fwd = fwd.at[tri[0]].set(tri[2], mode="drop")
        fwd = fwd.at[tri[1]].set(tri[2], mode="drop")
        for _ in range(16):            # pointer doubling to the fixpoint
            fwd = fwd[fwd]
        return fwd[res_map]

    _ADVANCE_CACHE[key] = root_map_advance
    return root_map_advance


def bank_advance_fn(cap: int, E: int, Pp: int, Tp: int):
    """Compiled one-batch adjacency-bank advance (ISSUE 9, DESIGN.md §9).

    ``(gids (E,), cnts (E,), size (cap,), selfc, nd, hgt, res_map (cap,),
    slab (8, Pp)) -> same seven carried arrays`` — all seven device arrays
    are donated so the bank truly advances in place; the (8, Pp) i32 slab is
    the only recurring upload (32 B per applied pair). The body is the pure
    `ref.bank_advance` twin; ``Tp`` pads the flattened entry workspace.
    """
    key = (cap, E, Pp, Tp)
    fn = _BANK_ADVANCE_CACHE.get(key)
    if fn is not None:
        return fn

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5, 6))
    def bank_advance(gids, cnts, size, selfc, nd, hgt, res_map, slab):
        return _ref.bank_advance(gids, cnts, size, selfc, nd, hgt, res_map,
                                 slab, Tp)

    _BANK_ADVANCE_CACHE[key] = bank_advance
    return bank_advance


def bank_grow_fn(E: int, newE: int):
    """Compiled pow2 regrow ``(gids (E,), cnts (E,)) -> ((newE,), (newE,))``.

    Device-to-device only — no host round trip, no transfer-counter bytes.
    No donation: the output shape differs from the input's, so XLA could
    never alias the buffers anyway (it would only warn). Tails are zero
    (cnt 0 entries are inert).
    """
    key = (E, newE)
    fn = _BANK_GROW_CACHE.get(key)
    if fn is not None:
        return fn

    @jax.jit
    def bank_grow(gids, cnts):
        g = jnp.zeros(newE, dtype=jnp.int32).at[:E].set(gids)
        c = jnp.zeros(newE, dtype=jnp.int32).at[:E].set(cnts)
        return g, c

    _BANK_GROW_CACHE[key] = bank_grow
    return bank_grow


def shingle_roots_fn(n: int, cap: int, m_edges: int):
    """Compiled ``(src, dst, res_map, a, b) -> (sh (cap,) u32, cnt (cap,)
    i32)`` — per-root shingle minima and per-root leaf counts.

    Matches the host twin exactly: node shingle = min(h(u), min over
    neighbors h(w)); root shingle = min over the root's leaves. Roots
    owning no leaves come back as the uint32 maximum with ``cnt == 0`` —
    the host substitutes the ``2^32 + id`` sentinel.
    """
    key = (n, cap, m_edges)
    fn = _SHINGLE_CACHE.get(key)
    if fn is not None:
        return fn

    @jax.jit
    def shingle_roots(src, dst, res_map, a, b):
        h_self = _hash_u32(jnp.arange(n, dtype=jnp.uint32), a, b)
        seg = jax.ops.segment_min(_hash_u32(dst, a, b), src, num_segments=n)
        node_sh = jnp.minimum(h_self, seg)
        roots = res_map[:n]
        sh = jax.ops.segment_min(node_sh, roots, num_segments=cap)
        cnt = jax.ops.segment_sum(jnp.ones(n, dtype=jnp.int32), roots,
                                  num_segments=cap)
        return sh, cnt

    _SHINGLE_CACHE[key] = shingle_roots
    return shingle_roots
