"""The merge forest of SLUGGER (Algorithm 2), written from the rules the
summarizer states for a seed, one candidate group at a time.

Iterations. Iteration t of T merges at threshold θ = 1/(1+t), the last at
θ = 0. Its seed stream is the t-th child of ``SeedSequence(seed)``, split
into a stream for the candidate groups and one for the merges.

Candidate groups. A leaf's shingle is the least 32-bit hash over itself
and its neighbours in the input graph; a root's is the least over its
leaves. Roots that share a shingle form a group (groups in ascending
shingle order, members in ascending id, singletons dropped). A group of
more than 500 is split again by a fresh shingle, at most ten times; what is
still too large is then shuffled and cut into pieces of 500.

Merging inside a group. Every member is a row over the group's columns:
the members themselves and their neighbour roots, with the subedge count
between them. The cost of a row is, over its columns, min(count,
possible - count + 1), plus the same for the subedges inside it, plus its
hierarchy edges. Saving(a, z) = 1 - cost(a+z) / (cost(a) + cost(z) -
cost of the pair), compared as an exact rational; a merge is accepted at
Saving >= θ, with θ taken as the next multiple of 2^-20. A row's partners
are ranked by an integer Jaccard key over its neighbour columns (the
quotient of intersection and union, both shifted until the union fits 15
bits); the best 16 are scored, the first best Saving wins.
- A group of at most 128 members merges in synchronous rounds: every row
  still active proposes its best partner; each proposal carries the
  priority splitmix64(group seed, round, row), and it is accepted when it
  holds the least priority of both rows it touches. A row that proposes
  nothing rests until it merges again.
- A larger group is swept in the order of a random queue: the row taken
  from the back is compared with the rest of the queue in queue order, and
  after a merge goes back to its front.

Ids. A group's merges are decided against the state at the start of the
iteration. At its end every group's r-th round (one merge is one round in
a swept group) is applied for r = 0, 1, ...: groups in order, pairs in
ascending row order, each pair under the next fresh id.
"""
from __future__ import annotations

import numpy as np

MAX_GROUP = 500
MAX_REHASH = 10
TOP_J = 16
ROUND_GROUP = 128
THETA_BITS = 20
KEY_BITS = 15
_M32 = 0xFFFFFFFF
_M64 = (1 << 64) - 1


def hash32(x: np.ndarray, sub_seed: int) -> np.ndarray:
    """The summarizer's 32-bit hash of vertex ids under one sub-seed."""
    a = np.uint64((2654435761 * (sub_seed | 1)) & _M32)
    b = np.uint64((sub_seed * 0x9E3779B9) & _M32)
    m = np.uint64(_M32)
    h = (np.asarray(x, dtype=np.uint64) * a + b) & m
    h ^= h >> np.uint64(16)
    h = (h * np.uint64(0x7FEB352D)) & m
    h ^= h >> np.uint64(15)
    return h


def priority(gseed: int, rnd: int, row: int) -> int:
    """splitmix64 of (group seed, round, row), the row kept in the low 8
    bits so that no two rows of a group tie."""
    x = gseed ^ (((rnd + 1) * 0x9E3779B97F4A7C15) & _M64)
    x = (x + row * 0xBF58476D1CE4E5B9) & _M64
    x ^= x >> 30
    x = (x * 0xBF58476D1CE4E5B9) & _M64
    x ^= x >> 27
    x = (x * 0x94D049BB133111EB) & _M64
    x ^= x >> 31
    return ((x << 8) | row) & _M64


def theta_steps(theta: float) -> int:
    """θ as a count of 2^-20 steps, rounded up."""
    p = int(np.ceil(theta * (1 << THETA_BITS)))
    return min(max(p, 0), 1 << THETA_BITS)


def _bf16(x) -> np.ndarray:
    import ml_dtypes

    return np.asarray(np.asarray(x, dtype=np.float32).astype(
        ml_dtypes.bfloat16), dtype=np.float32)


def _cost(cnt, poss):
    return np.minimum(cnt, poss - cnt + 1)


def _ranges(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """concat(arange(s, s + l)) over the pairs (s, l)."""
    ends = np.cumsum(lens)
    return np.repeat(starts - ends + lens, lens) + np.arange(int(ends[-1]) if
                                                             lens.size else 0)


def jaccard_keys(inter, deg_a, deg_b, precision: str) -> np.ndarray:
    inter = np.asarray(inter, dtype=np.int64)
    union = np.asarray(deg_a + deg_b - inter, dtype=np.int64)
    if precision == "bf16":
        return _bf16(inter / np.maximum(union, 1))
    bits = np.frexp(union.astype(np.float64))[1].astype(np.int64)
    sh = np.maximum(bits - KEY_BITS, 0)
    return ((inter >> sh) << KEY_BITS) // np.maximum(union >> sh, 1)


class Forest:
    """Roots with their size, inside subedges, hierarchy edges and
    neighbour counts; merged ids are appended."""

    def __init__(self, n: int, indptr: np.ndarray, indices: np.ndarray):
        self.n = n
        self.parent = [-1] * n
        self.size = [1] * n
        self.inside = [0] * n
        self.hier = [0] * n
        ind = indices.tolist()
        ptr = indptr.tolist()
        self.adj = {v: dict.fromkeys(ind[ptr[v]:ptr[v + 1]], 1)
                    for v in range(n)}

    def roots(self) -> np.ndarray:
        return np.flatnonzero(np.asarray(self.parent) == -1)

    def root_of_leaves(self) -> np.ndarray:
        par = np.asarray(self.parent, dtype=np.int64)
        r = np.arange(self.n, dtype=np.int64)
        while True:
            up = par[r]
            if (up < 0).all():
                return r
            r = np.where(up >= 0, up, r)

    def merge(self, a: int, z: int) -> int:
        m = len(self.parent)
        self.parent[a] = self.parent[z] = m
        self.parent.append(-1)
        self.size.append(self.size[a] + self.size[z])
        self.hier.append(self.hier[a] + self.hier[z] + 2)
        ra, rz = self.adj.pop(a), self.adj.pop(z)
        between = ra.pop(z, 0)
        rz.pop(a, None)
        self.inside.append(self.inside[a] + self.inside[z] + between)
        for x, c in rz.items():
            ra[x] = ra.get(x, 0) + c
        for x, c in ra.items():
            row = self.adj[x]
            row.pop(a, None)
            row.pop(z, None)
            row[m] = c
        self.adj[m] = ra
        return m


class Group:
    """One candidate group as dense rows; row i's own column is column i."""

    def __init__(self, f: Forest, members: np.ndarray, precision: str):
        self.precision = precision
        members = [int(x) for x in members]
        col = {m: i for i, m in enumerate(members)}
        for m in members:
            for x in f.adj[m]:
                if x not in col:
                    col[x] = len(col)
        k = len(members)
        self.cnt = np.zeros((k, len(col)), dtype=np.int64)
        for i, m in enumerate(members):
            row = f.adj[m]
            if row:
                idx = np.fromiter((col[x] for x in row), np.int64, len(row))
                self.cnt[i, idx] = np.fromiter(row.values(), np.int64,
                                               len(row))
        self.colsize = np.array([f.size[x] for x in col], dtype=np.int64)
        self.s = self.colsize[:k].copy()
        self.inside = np.array([f.inside[m] for m in members], dtype=np.int64)
        self.hier = np.array([f.hier[m] for m in members], dtype=np.int64)
        self.alive = np.ones(k, dtype=bool)
        # each row's neighbour columns, and how many there are
        self.nz = [np.flatnonzero(r) for r in self.cnt]
        self.deg = np.array([x.size for x in self.nz], dtype=np.int64)
        self.rounds: list = []

    def keys_to(self, rows: np.ndarray, others: np.ndarray) -> np.ndarray:
        """Jaccard keys of each of ``rows`` against each of ``others``,
        over neighbour columns."""
        if rows.size == 1:
            inter = np.count_nonzero(self.cnt[np.ix_(others, self.nz[rows[0]])],
                                     axis=1)[None, :]
        else:
            nb = (self.cnt > 0).astype(np.float64)
            inter = np.rint(nb[rows] @ nb[others].T).astype(np.int64)
        return jaccard_keys(inter, self.deg[rows][:, None],
                            self.deg[others][None, :], self.precision)

    def terms(self, a: np.ndarray, z: np.ndarray):
        """Saving(a, z) = 1 - numer / denom for pairs of rows, summed over
        the columns where a row has subedges (an empty column costs 0)."""
        need = np.unique(np.concatenate([a, z]))
        lens = self.deg[need]
        c = np.concatenate([self.nz[i] for i in need])
        r = np.repeat(np.arange(need.size), lens)
        v = self.cnt[need[r], c]
        ptr = np.zeros(need.size + 1, dtype=np.int64)
        np.cumsum(lens, out=ptr[1:])
        s = self.s[need]
        # sums of integers below 2^53: exact in the float64 of bincount
        cost = np.rint(np.bincount(
            r, weights=_cost(v, s[r] * self.colsize[c]),
            minlength=need.size)).astype(np.int64)
        cost += _cost(self.inside[need], s * (s - 1) // 2) + self.hier[need]
        ia, iz = np.searchsorted(need, a), np.searchsorted(need, z)
        pair = np.arange(a.size)
        la, lz = ptr[ia + 1] - ptr[ia], ptr[iz + 1] - ptr[iz]
        ent = np.concatenate([_ranges(ptr[ia], la), _ranges(ptr[iz], lz)])
        pid = np.concatenate([np.repeat(pair, la), np.repeat(pair, lz)])
        key = pid * np.int64(self.cnt.shape[1]) + c[ent]
        order = np.argsort(key, kind="stable")
        key, vals = key[order], v[ent][order]
        head = np.flatnonzero(np.concatenate([[True], key[1:] != key[:-1]]))
        merged = np.add.reduceat(vals, head) if head.size else vals
        pk, ck = pid[order][head], c[ent][order][head]
        sm = self.s[a] + self.s[z]
        fv = _cost(merged, sm[pk] * self.colsize[ck])
        fv[(ck == a[pk]) | (ck == z[pk])] = 0
        numer = np.rint(np.bincount(pk, weights=fv, minlength=a.size)
                        ).astype(np.int64)
        inside = self.inside[a] + self.inside[z] + self.cnt[a, z]
        numer += (_cost(inside, sm * (sm - 1) // 2) + self.hier[a]
                  + self.hier[z] + 2)
        denom = cost[ia] + cost[iz] - _cost(self.cnt[a, z],
                                            self.s[a] * self.s[z])
        return numer, denom

    def best(self, numer, denom, theta: float):
        """Index of the first best candidate of each row (rows along axis
        0, candidates in rank order along axis 1) and whether it is
        accepted at θ."""
        rows, J = numer.shape
        if denom.size and max(int(denom.max()), int(numer.max())) >= 1 << 31:
            raise OverflowError("a Saving term passed 2^31")
        valid = denom > 0
        has = np.zeros(rows, dtype=bool)
        best = np.full(rows, -1, dtype=np.int64)
        if self.precision == "bf16":
            sav = _bf16(1 - _bf16(numer / np.maximum(denom, 1)))
            top = np.zeros(rows, dtype=np.float32)
            for j in range(J):
                take = valid[:, j] & (~has | (sav[:, j] > top))
                top = np.where(take, sav[:, j], top)
                best = np.where(take, j, best)
                has |= take
            return best, has & (top >= np.float32(theta))
        nb = np.ones(rows, dtype=np.int64)
        db = np.ones(rows, dtype=np.int64)
        for j in range(J):
            n, d = numer[:, j], denom[:, j]
            take = valid[:, j] & (~has | (n * db < nb * d))
            nb, db = np.where(take, n, nb), np.where(take, d, db)
            best = np.where(take, j, best)
            has |= take
        th = theta_steps(theta)
        return best, has & (nb <= db) & (((db - nb) << THETA_BITS) >= th * db)

    def merge(self, a: int, z: int):
        between = self.cnt[a, z]
        moved = np.flatnonzero(self.cnt[:, z])
        self.cnt[a] += self.cnt[z]
        self.cnt[z] = 0
        self.cnt[:, a] += self.cnt[:, z]
        self.cnt[:, z] = 0
        self.cnt[a, a] = 0
        for r in set(moved.tolist()) | {a}:
            self.nz[r] = np.flatnonzero(self.cnt[r])
            self.deg[r] = self.nz[r].size
        self.nz[z] = self.nz[z][:0]
        self.deg[z] = 0
        self.inside[a] += self.inside[z] + between
        self.hier[a] += self.hier[z] + 2
        self.s[a] += self.s[z]
        self.colsize[a] = self.s[a]
        self.colsize[z] = 0
        self.alive[z] = False

    def merge_in_rounds(self, theta: float, gseed: int):
        active = self.alive.copy()
        rnd = 0
        while True:
            n_alive = int(self.alive.sum())
            rows = np.flatnonzero(active)
            if n_alive < 2 or rows.size == 0:
                return
            j = min(TOP_J, n_alive - 1)
            keys = self.keys_to(rows, np.arange(self.s.size))
            keys[:, ~self.alive] = -1
            keys[np.arange(rows.size), rows] = -1
            cand = np.argsort(-keys, axis=1, kind="stable")[:, :j]
            numer, denom = self.terms(np.repeat(rows, j), cand.ravel())
            best, acc = self.best(numer.reshape(-1, j), denom.reshape(-1, j),
                                  theta)
            props = [(int(rows[r]), int(cand[r, best[r]]),
                      priority(gseed, rnd, int(rows[r])))
                     for r in np.flatnonzero(acc)]
            active[rows[~acc]] = False
            if not props:
                return
            least: dict = {}
            for a, z, p in props:
                for x in (a, z):
                    least[x] = min(least.get(x, p), p)
            won = [(a, z) for a, z, p in props if least[a] == p == least[z]]
            for a, z in won:
                self.merge(a, z)
                active[z] = False
                active[a] = True
            self.rounds.append(won)
            rnd += 1

    def merge_by_queue(self, theta: float, rng: np.random.Generator):
        queue = rng.permutation(self.s.size).tolist()
        while len(queue) > 1:
            a = queue.pop()
            if not self.alive[a]:
                continue
            cand = np.array([q for q in queue if self.alive[q]],
                            dtype=np.int64)
            if cand.size == 0:
                return
            if cand.size > TOP_J:
                keys = self.keys_to(np.array([a]), cand)[0]
                cand = cand[np.argsort(-keys, kind="stable")[:TOP_J]]
            numer, denom = self.terms(np.full(cand.size, a), cand)
            best, acc = self.best(numer[None, :], denom[None, :], theta)
            if acc[0]:
                z = int(cand[best[0]])
                self.merge(a, z)
                self.rounds.append([(a, z)])
                queue.remove(z)
                queue.insert(0, a)


def _grouped(members: np.ndarray, keys: np.ndarray) -> list:
    """Members by key, groups in ascending key order, each in the members'
    order; groups of one are dropped."""
    order = np.argsort(keys, kind="stable")
    k, mem = keys[order], members[order]
    cut = np.flatnonzero(k[1:] != k[:-1]) + 1
    return [g for g in np.split(mem, cut) if g.size > 1]


def candidate_groups(f: Forest, leaf_nbrs, ss: np.random.SeedSequence):
    roots = f.roots()
    if roots.size < 2:
        return []
    streams = ss.spawn(MAX_REHASH + 2)
    sub = [int(c.generate_state(1, dtype=np.uint64)[0])
           for c in streams[:-1]]
    rng = np.random.default_rng(streams[-1])
    root_of = f.root_of_leaves()
    indptr, indices = leaf_nbrs

    def shingles(sub_seed: int) -> np.ndarray:
        h = hash32(np.arange(f.n), sub_seed)
        hn = hash32(indices, sub_seed)
        has = np.diff(indptr) > 0
        leaf = h.copy()
        leaf[has] = np.minimum(h[has], np.minimum.reduceat(
            hn, indptr[:-1][has]))
        out = np.full(len(f.parent), _M32 + 1, dtype=np.uint64)
        np.minimum.at(out, root_of, leaf)
        return out

    pending = _grouped(roots, shingles(sub[0])[roots])
    groups: list = []
    for rehash in range(1, MAX_REHASH + 2):
        groups += [g for g in pending if g.size <= MAX_GROUP]
        big = [g for g in pending if g.size > MAX_GROUP]
        if not big:
            return groups
        members = np.concatenate(big)
        which = np.repeat(np.arange(len(big)), [g.size for g in big])
        if rehash > MAX_REHASH:
            perm = rng.permutation(members.size)
            members, which = members[perm], which[perm]
            for i in range(len(big)):
                mine = members[which == i]
                groups += [mine[s:s + MAX_GROUP]
                           for s in range(0, mine.size, MAX_GROUP)
                           if mine[s:s + MAX_GROUP].size > 1]
            return groups
        sh = shingles(sub[rehash])[members].astype(np.int64)
        pending = _grouped(members, (which.astype(np.int64) << 33) | sh)
    return groups


def merge_forest(n: int, indptr: np.ndarray, indices: np.ndarray, T: int,
                 seed: int, precision: str = "exact") -> np.ndarray:
    """Parent of every id of the merge forest (-1 at a root)."""
    if precision not in ("exact", "bf16"):
        raise ValueError("precision is 'exact' or 'bf16'")
    f = Forest(n, indptr, indices)
    streams = np.random.SeedSequence(seed).spawn(max(T, 1))
    for t in range(1, T + 1):
        theta = 0.0 if t == T else 1.0 / (1 + t)
        ss_groups, ss_merge = streams[t - 1].spawn(2)
        groups = candidate_groups(f, (indptr, indices), ss_groups)
        if not groups:
            continue
        kids = ss_merge.spawn(len(groups))
        decided = []
        for g, ss in zip(groups, kids):
            grp = Group(f, g, precision)
            if g.size <= ROUND_GROUP:
                grp.merge_in_rounds(
                    theta, int(ss.generate_state(1, dtype=np.uint64)[0]))
            else:
                grp.merge_by_queue(theta, np.random.default_rng(ss))
            decided.append(([int(x) for x in g], grp.rounds))
        r = 0
        while True:
            pairs = [(cur, a, z) for cur, rounds in decided if r < len(rounds)
                     for a, z in sorted(rounds[r])]
            if not pairs:
                break
            for cur, a, z in pairs:
                cur[a] = f.merge(cur[a], cur[z])
            r += 1
    return np.asarray(f.parent, dtype=np.int64)
