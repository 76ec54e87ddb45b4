"""Pruning step (Sect. III-B4): remove supernodes that do not pay for their
h-edges, without any information loss.

  Step 1 — splice every non-leaf supernode with no incident p/n-edges
           (−1 h-edge each; −#children when it is a root).
  Step 2 — the paper's exactly-one-incident-non-loop-edge rule for roots:
           push the edge down to the children (guaranteed net reduction ≥ 1).
  Step 3 — the paper falls back to the *flat* encoding per root pair when
           cheaper. Our emission DP's per-pair cost is ≤ flat by construction
           (DESIGN.md §2.1), so the residual opportunity is in |H|: we
           generalize to a benefit-tested *root flattening* — remove a root,
           promote its children, re-attach its edges at child granularity —
           applied whenever it strictly reduces |P⁺|+|P⁻|+|H|.

Two interchangeable implementations (``prune(impl=...)``), equivalence
test-enforced:

  * ``_IRWork`` (default ``impl="ir"``) — flat arrays on the Summary IR
    (DESIGN.md §5). Steps 1 and 2 are vectorized mask passes over bincount
    degrees with pointer-jump splicing; step 3 precomputes every candidate's
    benefit delta in one bincount/reduceat sweep over the incidence CSR and
    walks candidates with an index cursor (no ``queue.pop(0)``), recomputing
    only candidates whose neighborhood a previous splice dirtied.
  * ``_Work`` (``impl="dict"``) — the original dict-of-set reference.

Determinism: both implementations process step-2 candidates in synchronized
passes (an edge whose two endpoints both qualify keeps the larger id) and
step-3 candidates in (depth desc, id asc) order, and both export edge rows
in canonical (lo, hi, sign) lexicographic order — two runs on the same
summary produce identical arrays, independent of dict/set iteration order.

All steps preserve the decompressed graph exactly (test-enforced).
"""
from __future__ import annotations

import numpy as np

from repro.core.summary import Summary
from repro.core.summary_ir import (SummaryIR, canon_edges, group_pairs,
                                   pair_key, pair_key_span, segmented_indices)


def _aggregate_pairs(ex, ey, ec):
    """Normalize (x, y) pairs, sum multiplicities, drop zero nets. Rows come
    out one per pair in (lo, hi) order: the canonical hand-off that every
    later pass (lookups, `to_summary`) takes as sorted."""
    lo = np.minimum(ex, ey)
    hi = np.maximum(ex, ey)
    # stable: every list this gets is sorted runs (a sorted hand-off plus the
    # rows a pass appends), which a stable sort merges in near-linear time;
    # the members of one pair are summed, so their order is not observed
    order, starts = group_pairs(lo, hi, stable=True)
    if lo.size == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z.copy(), z.copy()
    lo, hi, c = lo[order], hi[order], ec[order]
    sums = np.add.reduceat(c, starts)
    keep = sums != 0
    return lo[starts][keep], hi[starts][keep], sums[keep]


def _pair_lookup(bex, bey, bec, qx, qy, span=None, bkey=None, qkey=None):
    """Multiplicity of each query pair in the base pair list (0 if absent).

    Where the ids pack (`summary_ir.pair_key`, under ``span``, by default
    the lists' own), each query's key is found by binary search in the
    base's sorted keys. A base that is already in (lo, hi) order with one
    row per pair, as every pass hands it on, is not sorted again; a caller
    that looks up one list several times passes its key as ``bkey`` or
    ``qkey``, made under the same ``span``. Past the key's bound one shared
    lexsort aligns queries next to their base row, with no combined integer
    key, so arbitrarily large ids cannot overflow.
    """
    nq = qx.shape[0]
    if nq == 0 or bex.shape[0] == 0:
        return np.zeros(nq, dtype=np.int64)
    if span is None:
        span = pair_key_span(bex, bey, qx, qy)
    if qkey is None:
        qkey = pair_key(span, qx, qy)
    if qkey is not None:
        if bkey is None:
            bkey = pair_key(span, bex, bey)
        bval = bec
        if not bool(np.all(bkey[1:] > bkey[:-1])):
            # unsorted or repeated base pairs: sum each pair's rows
            order, starts = group_pairs(bex, bey)
            bkey = bkey[order][starts]
            bval = np.add.reduceat(bec[order], starts)
        j = np.searchsorted(bkey, qkey)
        j[j == bkey.shape[0]] = 0
        return np.where(bkey[j] == qkey, bval[j], 0)
    allx = np.concatenate([bex, qx])
    ally = np.concatenate([bey, qy])
    isq = np.zeros(allx.shape[0], dtype=np.int64)
    isq[bex.shape[0]:] = 1
    order = np.lexsort((isq, ally, allx))
    head = np.empty(allx.shape[0], dtype=bool)
    head[0] = True
    sx, sy = allx[order], ally[order]
    np.not_equal(sx[1:], sx[:-1], out=head[1:])
    head[1:] |= sy[1:] != sy[:-1]
    gid = np.cumsum(head) - 1
    vals = np.where(isq[order] == 0, np.concatenate([bec, np.zeros(nq, dtype=np.int64)])[order], 0)
    gval = np.zeros(gid[-1] + 1, dtype=np.int64)
    np.add.at(gval, gid, vals)
    out = np.empty(allx.shape[0], dtype=np.int64)
    out[order] = gval[gid]
    return out[bex.shape[0]:]


class _IRWork:
    """Array-based pruning working set over the flat Summary IR."""

    def __init__(self, s: Summary):
        self.n = s.n_leaves
        self.parent = np.asarray(s.parent, dtype=np.int64).copy()
        edges = np.asarray(s.edges, dtype=np.int64).reshape(-1, 3)
        # emission hands its edges on in canonical order, so grouping them
        # is one run-length pass, with no sort
        self.ex, self.ey, self.ec = _aggregate_pairs(
            edges[:, 0], edges[:, 1], edges[:, 2])

    # ---- helpers ----------------------------------------------------------
    def _cap(self) -> int:
        return self.parent.shape[0]

    def _alive(self) -> np.ndarray:
        return self.parent > -2

    def _nkids(self) -> np.ndarray:
        alive = self._alive()
        haspar = alive & (self.parent >= 0)
        return np.bincount(self.parent[haspar], minlength=self._cap())

    def _deg(self) -> np.ndarray:
        nonloop = self.ex != self.ey
        ends = np.concatenate([self.ex, self.ey[nonloop]])
        return np.bincount(ends, minlength=self._cap())

    def _splice(self, rem: np.ndarray):
        """Remove masked nodes; their children attach to the nearest kept
        ancestor (or become roots), via vectorized pointer jumping."""
        par = self.parent
        new_par = par.copy()
        mask = (new_par >= 0) & rem[new_par]
        while mask.any():
            new_par[mask] = par[new_par[mask]]
            mask = (new_par >= 0) & rem[new_par]
        new_par[rem] = -2
        self.parent = new_par

    # ---- step 1 -----------------------------------------------------------
    def step1(self) -> int:
        """One vectorized pass: splicing an edge-free node never changes any
        other node's degree or children, so the qualifying set is closed."""
        ids = np.arange(self._cap())
        rem = (self._alive() & (ids >= self.n) & (self._deg() == 0)
               & (self._nkids() > 0))
        if not rem.any():
            return 0
        self._splice(rem)
        return int(rem.sum())

    # ---- step 2 (paper Algorithm 3, lines 13-27) --------------------------
    def _step2_candidates(self):
        """Roots with exactly one incident edge, non-loop, multiplicity ±1.
        Returns (cands, eid, other, sign) after the larger-id conflict rule."""
        cap = self._cap()
        ids = np.arange(cap)
        nonloop = self.ex != self.ey
        ends = np.concatenate([self.ex, self.ey[nonloop]])
        eids = np.concatenate([np.arange(self.ex.shape[0], dtype=np.int64),
                               np.flatnonzero(nonloop)])
        inc_total = np.bincount(ends, minlength=cap)
        loop_cnt = np.bincount(self.ex[~nonloop], minlength=cap)
        cand_mask = (self._alive() & (self.parent == -1) & (ids >= self.n)
                     & (self._nkids() > 0) & (inc_total == 1) & (loop_cnt == 0))
        cands = np.flatnonzero(cand_mask)
        if cands.size == 0:
            return cands, cands, cands, cands
        # a candidate has exactly one incidence entry: scatter it, no sort
        at = np.full(cap, -1, dtype=np.int64)
        hit = cand_mask[ends]
        at[ends[hit]] = eids[hit]
        eid = at[cands]
        ok = np.abs(self.ec[eid]) == 1
        cands, eid = cands[ok], eid[ok]
        cand_mask = np.zeros(cap, dtype=bool)
        cand_mask[cands] = True
        other = self.ex[eid] + self.ey[eid] - cands
        keep = ~cand_mask[other] | (cands > other)
        cands, eid, other = cands[keep], eid[keep], other[keep]
        return cands, eid, other, np.sign(self.ec[eid])

    def step2(self) -> int:
        removed = 0
        while True:
            cands, eid, other, sg = self._step2_candidates()
            if cands.size == 0:
                return removed
            # push each candidate's single edge down to its children; the
            # rows go to _aggregate_pairs, which orders them, so the kids
            # are taken in id order
            slot = np.full(self._cap(), -1, dtype=np.int64)
            slot[cands] = np.arange(cands.size)
            kids = np.flatnonzero(self._alive() & (self.parent >= 0))
            own = slot[self.parent[kids]]
            new_x, own = kids[own >= 0], own[own >= 0]
            new_y = other[own]
            new_c = sg[own]
            keep = np.ones(self.ex.shape[0], dtype=bool)
            keep[eid] = False
            self.ex, self.ey, self.ec = _aggregate_pairs(
                np.concatenate([self.ex[keep], new_x]),
                np.concatenate([self.ey[keep], new_y]),
                np.concatenate([self.ec[keep], new_c]),
            )
            rem = np.zeros(self._cap(), dtype=bool)
            rem[cands] = True
            self._splice(rem)  # candidates are roots: children become roots
            removed += cands.size

    # ---- step 3 (benefit-tested splice of any non-leaf supernode) ----------
    def _step3_bulk(self, ir, cands, nk, sizes, bex, bey, bec, bkey, delta):
        """Bulk feasibility/plan/delta pass for one candidate subset.

        Emits the subset's plan rows (plo, phi, ps, pc) and accumulates each
        candidate's benefit delta into ``delta`` in place. Per-candidate
        outputs never interact, which is what lets `step3` run this per
        partition bucket with bit-identical results."""
        z = np.zeros(0, dtype=np.int64)
        if cands.size == 0:
            return z, z.copy(), z.copy(), z.copy()
        eids, seg = ir.incident_eids(cands)  # per-candidate incident edges
        a_of = cands[seg]
        loop_m = bex[eids] == bey[eids]
        # non-loop incident edges: plan (kid, b, sg) per kid of a
        nl = ~loop_m
        a_nl, e_nl = a_of[nl], eids[nl]
        b_nl = bex[e_nl] + bey[e_nl] - a_nl
        reps = nk[a_nl]
        kid_nl = ir.child_ids[segmented_indices(ir.child_ptr[a_nl], reps)]
        pu1 = kid_nl
        pv1 = np.repeat(b_nl, reps)
        ps1 = np.repeat(np.sign(bec[e_nl]), reps)
        pc1 = np.repeat(a_nl, reps)
        # self-loop incident edges: kid-pair expansion + kid self-loops
        a_lp = a_of[loop_m]
        e_lp = eids[loop_m]
        pu2 = [np.zeros(0, dtype=np.int64)]
        pv2 = [np.zeros(0, dtype=np.int64)]
        ps2 = [np.zeros(0, dtype=np.int64)]
        pc2 = [np.zeros(0, dtype=np.int64)]
        if a_lp.size:
            sg_lp = np.sign(bec[e_lp])
            for k in np.unique(nk[a_lp]):
                sel = nk[a_lp] == k
                aa, ss = a_lp[sel], sg_lp[sel]
                kid_rows = ir.child_ids[
                    ir.child_ptr[aa][:, None] + np.arange(int(k))[None, :]]
                iu, iv = np.triu_indices(int(k), k=1)
                pu2.append(kid_rows[:, iu].ravel())
                pv2.append(kid_rows[:, iv].ravel())
                ps2.append(np.repeat(ss, iu.size))
                pc2.append(np.repeat(aa, iu.size))
                big = sizes[kid_rows] > 1  # child self-loops for non-singletons
                pu2.append(kid_rows[big])
                pv2.append(kid_rows[big])
                ps2.append(np.repeat(ss, int(k))[big.ravel()])
                pc2.append(np.repeat(aa, int(k))[big.ravel()])
        pu = np.concatenate([pu1] + pu2)
        pv = np.concatenate([pv1] + pv2)
        ps = np.concatenate([ps1] + ps2)
        pc = np.concatenate([pc1] + pc2)
        plo, phi = np.minimum(pu, pv), np.maximum(pu, pv)
        cur = _pair_lookup(bex, bey, bec, plo, phi, span=self._cap(),
                           bkey=bkey)
        contrib = np.where(cur == -ps, -1, 1)
        np.add.at(delta, pc, contrib)
        return plo, phi, ps, pc

    def step3(self, partition_map=None) -> int:
        cap = self._cap()
        ir = SummaryIR(self.parent, self.n)
        nk = ir.n_children()
        ids = np.arange(cap)
        cand_mask = self._alive() & (ids >= self.n) & (nk > 0)
        cands = np.flatnonzero(cand_mask)
        if cands.size == 0:
            return 0
        # deterministic bottom-up order: deepest first, then ascending id
        # (stable over the ascending cands)
        cands = cands[np.argsort(-ir.depth[cands], kind="stable")]
        sizes = ir.size(ids)
        bex, bey, bec = self.ex, self.ey, self.ec
        # the base list is in (lo, hi) order, one row per pair
        # (_aggregate_pairs): key it once for every lookup below
        bkey = pair_key(cap, bex, bey)
        ir.build_incidence(np.stack([bex, bey, bec], axis=1))

        # -- bulk pass: feasibility, plans, deltas against the entry state --
        # Per-candidate outputs are independent, so the pass runs per
        # partition bucket when a partition map is given (DESIGN.md §8):
        # temporaries shrink to the bucket's plan size and the result is
        # bit-identical to the monolithic pass.
        bad = np.abs(bec) != 1
        bad_ends = np.concatenate([bex[bad], bey[bad & (bex != bey)]])
        infeasible_cnt = np.bincount(bad_ends, minlength=cap)
        deg_all = self._deg()
        is_root0 = self.parent == -1
        delta = np.where(is_root0, -nk, -1).astype(np.int64)
        delta = delta - deg_all

        if partition_map is None:
            buckets = [cands]
        else:
            part_of_cand = np.asarray(partition_map, dtype=np.int64)[
                ir.order[ir.first[cands]]]
            buckets = [cands[part_of_cand == p]
                       for p in np.unique(part_of_cand)]
        plo_b, phi_b, ps_b, pc_b = [], [], [], []
        for csub in buckets:
            plo_c, phi_c, ps_c, pc_c = self._step3_bulk(
                ir, csub, nk, sizes, bex, bey, bec, bkey, delta)
            plo_b.append(plo_c)
            phi_b.append(phi_c)
            ps_b.append(ps_c)
            pc_b.append(pc_c)
        plo = np.concatenate(plo_b)
        phi = np.concatenate(phi_b)
        ps = np.concatenate(ps_b)
        pc = np.concatenate(pc_b)
        # plan rows CSR by candidate (pc is emitted in ascending-candidate
        # runs per construction branch; re-sort to be safe)
        p_order = np.argsort(pc, kind="stable")
        plo, phi, ps, pc = plo[p_order], phi[p_order], ps[p_order], pc[p_order]
        p_counts = np.bincount(pc, minlength=cap)
        p_ptr = np.zeros(cap + 1, dtype=np.int64)
        np.cumsum(p_counts, out=p_ptr[1:])

        # -- sequential sweep with staleness tracking ------------------------
        overlay: dict = {}      # pair -> absolute current multiplicity
        extra_inc: dict = {}    # node -> overlay pairs not in the base list
        kids_mut: dict = {}     # node -> current child list (if changed)
        dirty = np.zeros(cap, dtype=bool)
        parent = self.parent

        def base_mult(x, y):
            # the base list is sorted by (lo, hi): search it in place
            lo = np.searchsorted(bex, x, side="left")
            hi = np.searchsorted(bex, x, side="right")
            j = lo + np.searchsorted(bey[lo:hi], y)
            if j < hi and bey[j] == y:
                return int(bec[j])
            return 0

        def mult(x, y):
            key = (int(min(x, y)), int(max(x, y)))
            if key in overlay:
                return overlay[key]
            return base_mult(*key)

        def kids_of(a):
            got = kids_mut.get(a)
            if got is not None:
                return got
            return ir.children_of(a).tolist()

        def incident_pairs(a):
            out = []
            ee, _ = ir.incident_eids(np.array([a], dtype=np.int64))
            for e in ee:
                key = (int(bex[e]), int(bey[e]))
                c = overlay.get(key)
                c = int(bec[e]) if c is None else c
                if c != 0:
                    out.append((key[0], key[1], c))
            for key in extra_inc.get(a, ()):
                c = overlay.get(key, 0)
                if c != 0:
                    out.append((key[0], key[1], c))
            return out

        def set_mult(x, y, value):
            key = (int(min(x, y)), int(max(x, y)))
            if key not in overlay and base_mult(*key) == 0:
                extra_inc.setdefault(key[0], set()).add(key)
                if key[0] != key[1]:
                    extra_inc.setdefault(key[1], set()).add(key)
            overlay[key] = value

        def eval_one(a):
            """(accept, removals, plan) from the *current* state — the same
            benefit test as the bulk pass, for dirtied candidates."""
            kids = kids_of(a)
            inc = incident_pairs(a)
            is_root = parent[a] == -1
            d = -len(kids) if is_root else -1
            plan = []
            for (x, y, c) in inc:
                if abs(c) != 1:
                    return False, None, None
                sg = 1 if c > 0 else -1
                d -= 1
                if x == y:
                    for i in range(len(kids)):
                        for j in range(i + 1, len(kids)):
                            plan.append((kids[i], kids[j], sg))
                    for kk in kids:
                        if sizes[kk] > 1:
                            plan.append((kk, kk, sg))
                else:
                    b = y if x == a else x
                    for kk in kids:
                        plan.append((kk, b, sg))
            for (u, v, sg) in plan:
                d += -1 if mult(u, v) == -sg else 1
            accept = d <= 0 and (d < 0 or not is_root)
            return accept, inc, plan

        removed = 0
        for a in cands:
            a = int(a)
            if dirty[a]:
                accept, inc, plan = eval_one(a)
                if not accept:
                    continue
            else:
                if infeasible_cnt[a] or not (
                    delta[a] <= 0 and (delta[a] < 0 or parent[a] != -1)
                ):
                    continue
                inc = incident_pairs(a)
                s, e = p_ptr[a], p_ptr[a + 1]
                plan = list(zip(plo[s:e].tolist(), phi[s:e].tolist(), ps[s:e].tolist()))
            # apply: drop a's edges, add the plan at child granularity
            touched = set()
            for (x, y, _c) in inc:
                set_mult(x, y, 0)
                touched.add(x)
                touched.add(y)
            for (u, v, sg) in plan:
                set_mult(u, v, mult(u, v) + sg)
                touched.add(u)
                touched.add(v)
            kids = kids_of(a)
            p = int(parent[a])
            for kk in kids:
                parent[kk] = p
            if p >= 0:
                pk = kids_of(p)
                pk = [k for k in pk if k != a] + list(kids)
                kids_mut[p] = pk
                dirty[p] = True
            parent[a] = -2
            for w in sorted(touched):
                dirty[w] = True
                if parent[w] >= 0:
                    dirty[parent[w]] = True
            for kk in kids:
                dirty[kk] = True
            removed += 1

        if overlay:
            ov = sorted(overlay.items())
            ovx = np.array([k[0] for k, _ in ov], dtype=np.int64)
            ovy = np.array([k[1] for k, _ in ov], dtype=np.int64)
            ovc = np.array([v for _, v in ov], dtype=np.int64)
            # overlay values are absolute: drop overlaid base rows, then add
            overlaid = _pair_lookup(ovx, ovy, np.ones_like(ovc), bex, bey,
                                    span=cap, qkey=bkey) > 0
            nz = ovc != 0
            self.ex, self.ey, self.ec = _aggregate_pairs(
                np.concatenate([bex[~overlaid], ovx[nz]]),
                np.concatenate([bey[~overlaid], ovy[nz]]),
                np.concatenate([bec[~overlaid], ovc[nz]]),
            )
        return removed

    # ---- export ------------------------------------------------------------
    def to_summary(self) -> Summary:
        # the pairs are in (lo, hi) order, one row each with one sign
        # (_aggregate_pairs), so expanding the multiplicities in place is
        # the canonical (lo, hi, sign) order: no sort
        reps = np.abs(self.ec)
        rows = np.stack([
            np.repeat(self.ex, reps),
            np.repeat(self.ey, reps),
            np.repeat(np.sign(self.ec), reps),
        ], axis=1)
        return Summary(n_leaves=self.n, parent=self.parent, edges=rows)


class _Work:
    """Dict-of-set reference implementation (kept for equivalence tests)."""

    def __init__(self, s: Summary):
        self.n = s.n_leaves
        self.parent = {i: int(p) for i, p in enumerate(s.parent) if p != -2}
        self.children: dict = {}
        for i, p in self.parent.items():
            if p >= 0:
                self.children.setdefault(p, []).append(i)
        # signed multiplicity per normalized pair
        self.edges: dict = {}
        for X, Y, sg in s.edges:
            k = (int(min(X, Y)), int(max(X, Y)))
            self.edges[k] = self.edges.get(k, 0) + int(sg)
            if self.edges[k] == 0:
                del self.edges[k]
        self.incident: dict = {}
        for (X, Y), c in self.edges.items():
            self.incident.setdefault(X, set()).add((X, Y))
            if X != Y:
                self.incident.setdefault(Y, set()).add((X, Y))
        self._size: dict = {}

    # ---- helpers ----------------------------------------------------------
    def size(self, x: int) -> int:
        if x in self._size:
            return self._size[x]
        r = 1 if x < self.n else sum(self.size(c) for c in self.children.get(x, []))
        self._size[x] = r
        return r

    def deg(self, x: int) -> int:
        return len(self.incident.get(x, ()))

    def _add(self, X: int, Y: int, sg: int):
        k = (min(X, Y), max(X, Y))
        c = self.edges.get(k, 0) + sg
        if c == 0:
            self.edges.pop(k, None)
            self.incident.get(k[0], set()).discard(k)
            if k[0] != k[1]:
                self.incident.get(k[1], set()).discard(k)
        else:
            self.edges[k] = c
            self.incident.setdefault(k[0], set()).add(k)
            if k[0] != k[1]:
                self.incident.setdefault(k[1], set()).add(k)

    def _remove_node(self, a: int):
        """Splice a out of the forest; children attach to a's parent."""
        p = self.parent[a]
        for c in self.children.get(a, []):
            self.parent[c] = p
            if p >= 0:
                self.children.setdefault(p, []).append(c)
        if p >= 0 and a in self.children.get(p, []):
            self.children[p].remove(a)
        self.children.pop(a, None)
        del self.parent[a]
        self._size.clear()

    # ---- step 1 -----------------------------------------------------------
    def step1(self) -> int:
        removed = 0
        queue = [x for x in list(self.parent) if x >= self.n]
        while queue:
            a = queue.pop()
            if a not in self.parent or a < self.n:
                continue
            if self.deg(a) == 0 and self.children.get(a):
                p = self.parent[a]
                kids = list(self.children[a])
                self._remove_node(a)
                removed += 1
                if p >= 0:
                    queue.append(p)
                queue.extend(k for k in kids if k >= self.n)
        return removed

    # ---- step 2 (paper Algorithm 3, lines 13-27) --------------------------
    def step2(self) -> int:
        """Pass-synchronous: each pass snapshots the qualifying roots, drops
        the smaller endpoint when one edge connects two of them, then applies
        all push-downs — matching `_IRWork.step2` bit for bit."""
        removed = 0
        while True:
            quals = {}
            for a, p in self.parent.items():
                if p != -1 or a < self.n or not self.children.get(a):
                    continue
                inc = list(self.incident.get(a, ()))
                nonloop = [e for e in inc if e[0] != e[1]]
                if len(inc) != 1 or len(nonloop) != 1 or abs(self.edges[nonloop[0]]) != 1:
                    continue
                (X, Y) = nonloop[0]
                quals[a] = (X, Y, Y if X == a else X)
            if not quals:
                return removed
            batch = [(a, X, Y, b) for a, (X, Y, b) in quals.items()
                     if b not in quals or a > b]
            for a, X, Y, b in batch:
                sg = 1 if self.edges[(X, Y)] > 0 else -1
                kids = list(self.children[a])
                self._add(X, Y, -self.edges[(X, Y)])
                for c in kids:
                    self._add(c, b, sg)
                self._remove_node(a)
                removed += 1

    # ---- step 3 (benefit-tested splice of any non-leaf supernode) ----------
    def _depth(self, x: int) -> int:
        d = 0
        while self.parent.get(x, -1) >= 0:
            x = self.parent[x]
            d += 1
        return d

    def step3(self) -> int:
        removed = 0
        nodes = [x for x in sorted(self.parent) if x >= self.n and self.children.get(x)]
        # bottom-up: splice deepest first so parents see their final child
        # lists; ties broken by ascending id (stable sort over sorted ids)
        nodes.sort(key=self._depth, reverse=True)
        i = 0
        while i < len(nodes):
            a = nodes[i]
            i += 1
            if a not in self.parent or not self.children.get(a):
                continue
            kids = list(self.children[a])
            big_kids = [c for c in kids if self.size(c) > 1]
            is_root = self.parent[a] == -1
            # h-edges saved: every child edge when a is a root (children get no
            # replacement parent), else just a's own parent edge.
            delta = -len(kids) if is_root else -1
            plan: list = []
            feasible = True
            for (X, Y) in list(self.incident.get(a, ())):
                cur = self.edges[(X, Y)]
                if abs(cur) != 1:
                    feasible = False
                    break
                sg = 1 if cur > 0 else -1
                delta -= 1  # the removed edge itself
                if X == Y:  # self-loop: expand to child pairs + child loops
                    for ii in range(len(kids)):
                        for jj in range(ii + 1, len(kids)):
                            plan.append((kids[ii], kids[jj], sg))
                    for c in big_kids:
                        plan.append((c, c, sg))
                else:
                    b = Y if X == a else X
                    for c in kids:
                        plan.append((c, b, sg))
            if not feasible:
                continue
            for (u, v, sg) in plan:
                k = (min(u, v), max(u, v))
                delta += -1 if self.edges.get(k, 0) == -sg else 1
            if delta <= 0 and (delta < 0 or not is_root):
                for (X, Y) in list(self.incident.get(a, ())):
                    self._add(X, Y, -self.edges[(X, Y)])
                for (u, v, sg) in plan:
                    self._add(u, v, sg)
                self._remove_node(a)
                removed += 1
        return removed

    # ---- export ------------------------------------------------------------
    def to_summary(self, total_ids: int) -> Summary:
        parent = np.full(total_ids, -2, dtype=np.int64)
        for x, p in self.parent.items():
            parent[x] = p
        rows = []
        for (X, Y), c in self.edges.items():
            sg = 1 if c > 0 else -1
            for _ in range(abs(c)):
                rows.append((X, Y, sg))
        edges = (np.array(rows, dtype=np.int64) if rows
                 else np.zeros((0, 3), dtype=np.int64))
        return Summary(n_leaves=self.n, parent=parent, edges=canon_edges(edges))


def prune(summary: Summary, steps=(1, 2, 3), rounds: int = 3,
          impl: str = "ir", partition_map=None) -> Summary:
    """Run the selected pruning substeps (repeated until fixpoint, ≤ rounds).

    ``impl="ir"`` (default) runs the vectorized array implementation;
    ``impl="dict"`` the dict-of-set reference. Both produce bit-identical
    summaries (test-enforced). ``partition_map`` (node → partition,
    DESIGN.md §8) makes the step-3 bulk pass run per partition bucket —
    bounded temporaries, bit-identical output; the dict reference ignores
    it."""
    if impl not in ("ir", "dict"):
        raise ValueError(f"unknown prune impl {impl!r}; use 'ir' or 'dict'")
    w = _IRWork(summary) if impl == "ir" else _Work(summary)
    for _ in range(rounds):
        changed = 0
        if 1 in steps:
            changed += w.step1()
        if 2 in steps:
            changed += w.step2()
        if 3 in steps:
            if impl == "ir":
                changed += w.step3(partition_map=partition_map)
            else:
                changed += w.step3()
        if not changed:
            break
    if impl == "ir":
        return w.to_summary()
    return w.to_summary(summary.parent.shape[0])
