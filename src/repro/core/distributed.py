"""Distributed (JAX) engine pieces for SLUGGER.

Deployment story (DESIGN.md §2.2/§6/§8): the O(|E|) scans (hashing,
segment-min shingles) and the O(k²) in-group scoring are device-side,
sharded with ``shard_map`` over the mesh's data axis; only the tiny,
inherently sequential merge decisions run on host. On a real pod the edge
list lives sharded in HBM and never leaves the devices; the host sees
(n_roots,) shingles and per-group top-pairs.

`shingle_provider` and `batched_intersections_mesh` are the production
hooks: the `SummarizerEngine` plugs them into its shingle stage and its
candidate ranking whenever ``backend="batched"`` sees more than one device
(or an explicit mesh) — this module is the engine's multi-device path, not
a stand-alone demo.

Engines:
  * ``shingles_sharded``     — edge-sharded minhash shingles (pmin combine)
  * ``shingle_provider``     — the engine hook: sharded shingles + host
                               root segment-min + leafless-root sentinel
  * ``batched_intersections_mesh`` — (B, G, W) bitset batches shard_map'd
                               over the data axis, masked kernel per shard
                               (padding early-exits; transfer-only)
  * ``greedy_group_matching``— vmapped on-device greedy matching per group
  * ``summarize_jax``        — hybrid engine: device scoring + host decisions,
                               exactness restored by the emission DP
  * ``summarize_step_fn``    — the jit-able step used by the multi-pod dry-run
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.slugger import SluggerState, _emit_encoding
from repro.core.minhash import rootwise_min
from repro.core.pruning import prune
from repro.graphs.csr import Graph

MAXU = jnp.uint32(0xFFFFFFFF)


def _hash_u32(x, a, b):
    h = x.astype(jnp.uint32) * jnp.uint32(a) + jnp.uint32(b)
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> jnp.uint32(15))
    return h


def node_shingles_dense(src, dst, n, a, b):
    """Replicated-reference shingle computation (src/dst = directed edges)."""
    h_self = _hash_u32(jnp.arange(n, dtype=jnp.uint32), a, b)
    h_nbr = _hash_u32(dst.astype(jnp.uint32), a, b)
    seg = jax.ops.segment_min(h_nbr, src, num_segments=n)
    return jnp.minimum(h_self, seg)


def shingles_sharded(mesh, data_axes=("data",)):
    """Edge-sharded shingles: local segment-min + cross-shard pmin.

    Returns a function (src, dst, n_static, a, b) -> (n,) uint32, where the
    edge arrays are sharded along ``data_axes`` and padded with src == n
    (padding rows fold into a dummy segment).
    """

    def _local(src, dst, h_self, a, b):
        n = h_self.shape[0]
        h_nbr = _hash_u32(dst.astype(jnp.uint32), a, b)
        seg = jax.ops.segment_min(h_nbr, src, num_segments=n + 1)[:n]
        local = jnp.minimum(h_self, seg)
        for ax in data_axes:
            local = jax.lax.pmin(local, ax)
        return local

    def fn(src, dst, n, a, b):
        h_self = _hash_u32(jnp.arange(n, dtype=jnp.uint32), a, b)
        edge_spec = P(data_axes if len(data_axes) > 1 else data_axes[0])
        return jax.shard_map(
            functools.partial(_local, a=a, b=b),
            mesh=mesh,
            in_specs=(edge_spec, edge_spec, P(None)),
            out_specs=P(None),
        )(src, dst, h_self)

    return fn


def root_shingles_jax(node_sh, root_of, n_ids):
    return jax.ops.segment_min(node_sh, root_of, num_segments=n_ids)


def _data_axes_of(mesh, data_axes):
    if data_axes is not None:
        return tuple(data_axes)
    from repro.launch.mesh import dp_axes_of
    return dp_axes_of(mesh)


def shingle_provider(g: Graph, mesh, data_axes=None):
    """Engine hook: mesh-sharded shingle computation (DESIGN.md §8).

    Uploads the padded, edge-sharded adjacency once; returns
    ``for_roots(root_of) -> shingle_fn(sub_seed, n_ids)`` matching the
    `minhash.candidate_groups` provider protocol. Node-level minima come
    from the `shingles_sharded` shard_map (local segment-min + cross-shard
    pmin); the root-level segment-min and the leafless-root sentinel run on
    host via the same `rootwise_min` the host path uses. Sentinels are
    ``2^32 + id`` — device hashes are uint32, so they can never collide.
    """
    data_axes = _data_axes_of(mesh, data_axes)
    n_shards = int(np.prod([mesh.shape[a] for a in data_axes]))
    src = np.repeat(np.arange(g.n), np.diff(g.indptr)).astype(np.int32)
    dst = np.asarray(g.indices, dtype=np.int32)
    pad = (-src.size) % max(n_shards, 1)
    src_p = jnp.asarray(np.concatenate([src, np.full(pad, g.n, np.int32)]))
    dst_p = jnp.asarray(np.concatenate([dst, np.zeros(pad, np.int32)]))
    sharded = shingles_sharded(mesh, data_axes)

    def for_roots(root_of: np.ndarray):
        root_of = np.asarray(root_of, dtype=np.int64)

        def shingle_fn(sub_seed: int, n_ids: int) -> np.ndarray:
            a = np.uint32((2654435761 * (int(sub_seed) | 1)) & 0xFFFFFFFF)
            b = np.uint32((int(sub_seed) * 0x9E3779B9) & 0xFFFFFFFF)
            node_sh = np.asarray(sharded(src_p, dst_p, g.n, a, b))
            return rootwise_min(node_sh.astype(np.int64), root_of, n_ids,
                                1 << 32)

        return shingle_fn

    return for_roots


from repro.kernels.common import LruCache, mesh_content_key, shard_map_no_check

_MESH_JACCARD_CACHE = LruCache(8)  # compiled shard_map executables, by shape


def batched_intersections_mesh(mesh, data_axes=None):
    """Engine hook: the bitset intersection dispatch shard_map'd over the
    mesh — the ``backend="batched"`` ranking source.

    Returns ``fn((B, G, W) uint32) -> (B, G, G) int64``: the batch is
    padded to a pow2 multiple of the shard count (jit-cache shaping), each
    shard runs `batch_masked_intersection_kernel` on its slice with its OWN
    valid-row count — real rows live in a contiguous prefix, so shard s of
    size Bs holds ``clip(B − s·Bs, 0, Bs)`` of them and the padded rows
    early-exit before the O(G²·W) popcount: padding is transfer-only
    (ISSUE 5). Intersection counts are exact integers, so merge decisions
    are bit-identical to the host ranking given the same bitmaps. Transfers
    report to `core.transfer.GLOBAL` (one ranking round per dispatch).
    """
    from repro.core.transfer import GLOBAL as TRANSFER
    from repro.kernels.bitset_jaccard.kernel import (
        batch_masked_intersection_kernel)
    from repro.kernels.common import default_interpret, pow2

    data_axes = _data_axes_of(mesh, data_axes)
    n_shards = int(np.prod([mesh.shape[a] for a in data_axes]))
    spec = P(data_axes if len(data_axes) > 1 else data_axes[0])
    mesh_key = mesh_content_key(mesh)

    def fn(bits: np.ndarray) -> np.ndarray:
        B, G, W = bits.shape
        Wp = pow2(W)
        # pad the batch to a pow2 multiple of the shard count so the jit
        # cache stays small (same rule as the single-device ops tiling)
        Bs = pow2((B + n_shards - 1) // n_shards, floor=1)
        Bp = n_shards * Bs
        batch = np.zeros((Bp, G, Wp), dtype=np.uint32)
        batch[:B, :, :W] = bits
        # per-shard valid-row counts (real rows are a contiguous prefix);
        # shipped as a sharded input so the compiled fn is B-agnostic
        valid = np.clip(B - np.arange(n_shards, dtype=np.int64) * Bs,
                        0, Bs).astype(np.int32)
        key = (mesh_key, Bp, G, Wp)
        f = _MESH_JACCARD_CACHE.get(key)
        if f is None:
            interpret = default_interpret()

            def local(bb, vv):
                return batch_masked_intersection_kernel(bb, vv,
                                                        interpret=interpret)

            f = jax.jit(shard_map_no_check(local, mesh, (spec, spec), spec))
            _MESH_JACCARD_CACHE[key] = f
        TRANSFER.add_h2d(batch.nbytes + valid.nbytes)
        inter = np.asarray(f(batch, valid))
        TRANSFER.add_d2h(inter.nbytes)
        TRANSFER.tick_round()
        return inter[:B].astype(np.int64)

    return fn


# --------------------------------------------------------------------------
# On-device greedy matching within padded candidate groups
# --------------------------------------------------------------------------
def _match_one_group(scores, threshold, max_merges):
    """Greedy maximum-score matching on a (K, K) score matrix.

    Returns (max_merges, 2) int32 pair indices, padded with -1.
    """
    K = scores.shape[0]
    scores = jnp.where(jnp.eye(K, dtype=bool), -jnp.inf, scores)

    def body(carry, _):
        sc, out, i = carry
        flat = jnp.argmax(sc)
        r, c = flat // K, flat % K
        ok = sc[r, c] >= threshold
        pair = jnp.where(ok, jnp.array([r, c], dtype=jnp.int32), jnp.array([-1, -1], dtype=jnp.int32))
        # mask the merged pair's rows/cols
        mask_r = (jnp.arange(K) == r) | (jnp.arange(K) == c)
        sc = jnp.where(ok & (mask_r[:, None] | mask_r[None, :]), -jnp.inf, sc)
        out = out.at[i].set(pair)
        return (sc, out, i + 1), None

    out0 = jnp.full((max_merges, 2), -1, dtype=jnp.int32)
    (_, out, _), _ = jax.lax.scan(body, (scores, out0, 0), None, length=max_merges)
    return out


def greedy_group_matching(scores, threshold, max_merges=None):
    """vmapped greedy matching: scores (G, K, K) -> (G, max_merges, 2)."""
    G, K, _ = scores.shape
    if max_merges is None:
        max_merges = K // 2
    return jax.vmap(lambda s: _match_one_group(s, threshold, max_merges))(scores)


def _pack_bits_jax(memb_cols):
    """(G, K, R) bool -> (G, K, W) uint32 packed."""
    G, K, R = memb_cols.shape
    W = (R + 31) // 32
    pad = W * 32 - R
    m = jnp.pad(memb_cols, ((0, 0), (0, 0), (0, pad)))
    m = m.reshape(G, K, W, 32).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return (m * weights).sum(axis=-1).astype(jnp.uint32)


def group_jaccard_scores(nbr_onehot):
    """nbr_onehot: (G, K, R) bool neighbor indicators per group member.
    Returns (G, K, K) Jaccard matrices (einsum form — MXU-friendly)."""
    x = nbr_onehot.astype(jnp.float32)
    inter = jnp.einsum("gkr,glr->gkl", x, x)
    deg = x.sum(-1)
    union = deg[:, :, None] + deg[:, None, :] - inter
    return jnp.where(union > 0, inter / jnp.maximum(union, 1.0), 0.0)


# --------------------------------------------------------------------------
# The jit-able candidate-generation step used by the multi-pod dry-run
# --------------------------------------------------------------------------
def summarize_step_fn(n_nodes: int, hist: str = "sort"):
    """One SLUGGER candidate-generation + scoring step over a sharded edge
    list: shingles → candidate-group-size histogram. Lowered/compiled in the
    dry-run.

    ``hist``:
      * "sort"    — exact group sizes via jnp.unique (paper-faithful baseline;
        the sort's O(n log n) merge passes dominate HBM traffic),
      * "scatter" — §Perf iteration: hash shingles into n/500 buckets and
        scatter-add ones (O(n) traffic). Group sizes become bucket sizes —
        exactly the cap-at-500 random split the paper applies anyway
        (Sect. III-B2), so downstream semantics are unchanged.
    """

    def step(src, dst, root_of, seed):
        a = jnp.uint32(2654435761) * (seed.astype(jnp.uint32) | jnp.uint32(1))
        b = seed.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
        h_self = _hash_u32(jnp.arange(n_nodes, dtype=jnp.uint32), a, b)
        h_nbr = _hash_u32(dst.astype(jnp.uint32), a, b)
        seg = jax.ops.segment_min(h_nbr, src, num_segments=n_nodes + 1)[:n_nodes]
        node_sh = jnp.minimum(h_self, seg)
        root_sh = jax.ops.segment_min(node_sh, root_of, num_segments=n_nodes)
        if hist == "scatter":
            n_buckets = max(n_nodes // 500, 1)
            bucket = (_hash_u32(root_sh, a ^ jnp.uint32(0xA5A5A5A5), b) % jnp.uint32(n_buckets)).astype(jnp.int32)
            counts = jax.ops.segment_sum(jnp.ones_like(bucket), bucket, num_segments=n_buckets)
            return root_sh, counts[bucket]
        # group-size histogram (how full candidate sets are)
        _, inv, counts = jnp.unique(
            root_sh, return_inverse=True, return_counts=True, size=n_nodes, fill_value=MAXU
        )
        return root_sh, counts[inv]

    return step


# --------------------------------------------------------------------------
# Hybrid engine: device scoring, host decisions, DP emission for exactness
# --------------------------------------------------------------------------
def summarize_jax(
    g: Graph,
    T: int = 20,
    seed: int = 0,
    max_group: int = 128,
    prune_steps=(1, 2, 3),
    min_jaccard: float = 0.05,
):
    """Approximate-selection engine (merge picks by device-side Jaccard
    matching, verified by host-side Saving ≥ θ). Lossless by construction —
    the emission DP re-encodes the exact input graph."""
    from repro.core.merging import GroupWorkspace
    from repro.core.minhash import candidate_groups

    state = SluggerState(g)
    iter_streams = np.random.SeedSequence((seed, 31337)).spawn(max(T, 1))
    for t in range(1, T + 1):
        theta = 0.0 if t == T else 1.0 / (1 + t)
        alive = state.alive
        groups = candidate_groups(g, state.root_of, alive,
                                  seed=iter_streams[t - 1], max_group=max_group)
        if not groups:
            continue
        K = max(len(gr) for gr in groups)
        for grp in groups:
            ws = GroupWorkspace(state, grp)
            k = len(grp)
            R = ws.CNT.shape[1]
            onehot = (ws.CNT > 0)[None, :, :]
            scores = group_jaccard_scores(jnp.asarray(onehot))
            pairs = np.asarray(greedy_group_matching(scores, min_jaccard, max_merges=k // 2))[0]
            for r, c in pairs:
                if r < 0:
                    break
                if not (ws.alive[r] and ws.alive[c]):
                    continue
                sav = ws.savings(int(r), np.array([int(c)]))
                if sav[0] >= theta:
                    ws.merge(int(r), int(c))
    summary = _emit_encoding(state)
    if prune_steps:
        summary = prune(summary, steps=prune_steps)
    return summary
