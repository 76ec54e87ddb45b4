"""Device-resident merge rounds: persistent bitmap+count arenas (§9).

`ResidentBitmapArena` is the ``backend="resident"`` engine's device half.
One arena wraps ONE batched workspace chunk (`merging.BatchedGroupWorkspace`,
a (B, G, W) packed-bitmap batch). Since ISSUE 7 the arena holds the WHOLE
merge-round state — bitmaps AND the exact integer count tensors (``CNT``,
column sizes, member columns, sizes, self-counts, descendant counts,
heights, row costs, the dirty queue) — so a full sweep round is two
on-device ops:

1. **fused proposal round** (`kernels/bitset_fold.round_fn`): the device
   derives the dirty-row list from its own ``dirty`` mirror, ranks
   candidates by the quantized-Jaccard key, evaluates the EXACT integer
   Saving of each (32-bit-limb rational compare) and applies the
   quantized-θ̂ acceptance; only (K, 2) int8 ``[accept, partner]`` rows
   come back — no dirty-row upload, no score download;
2. **count-carrying fold** (`kernels/bitset_fold.fold_counts_fn`): the
   round's accepted pairs fold bitmaps, counts, stats and row costs in
   place (donated buffers), mirroring the host `apply_merges` phases
   bit-for-bit.

Only the conflict-free matching stays on host (it needs the group-seed
hashes), so per round the boundary carries the accepted-pair instruction
slab up and the per-dirty-row verdict down. The legacy v1 protocol
(`topj_rows` ranking + bitmap-only `fold`) remains for tests and tools.

Since ISSUE 9 the per-iteration workspace upload is gone too:
`ResidentAdjacencyBank` carries every root's coalesced adjacency row on
device ACROSS iterations (append-only ``gid``/``cnt`` streams advanced
straight from the applied `MergePlan` batches), and
`ResidentBitmapArena.from_bank` EXTRACTS each chunk's (B, G, W) bitmaps and
count tensors on device — the host workspaces become shape-only shells and
the device bank is authoritative within a run. The host materializes bank
rows only for verification (`host_rows`, the `sync_rows`-style contract).

`sync_rows` keeps the verification contract: tests pull selected rows back
and assert the device fold is bit-identical to the host fold.

Every upload/download reports to `core.transfer.GLOBAL` under a lifecycle
phase (``init``/``upload``/``rank``/``fold``/``carry``/``candgen``/
``bank``/``extract``/``sync``), and each proposal round-trip ticks the
round counter — `benchmarks/scalability.py --resident` gates the
bytes-per-iteration reduction on these numbers.
"""
from __future__ import annotations

import logging

import numpy as np

from repro import faults
from repro.core.transfer import GLOBAL as TRANSFER

log = logging.getLogger("repro.engine")


def _jax():
    try:
        import jax
    except ImportError as e:  # pragma: no cover - jax is a hard dep of this path
        raise RuntimeError(
            "backend='resident' needs jax; install jax or use "
            "backend='numpy'") from e
    return jax


def _run_round_op(arena, site: str, build, args):
    """Run one compiled round op; a failed Pallas dispatch retries ONCE on
    the jnp `ref.py` twin (§11 degradation policy — bit-identical by the
    kernel twin contract), dropping ``use_kernel`` for the arena's life.
    The op is lowered and compiled BEFORE the retry scope: a kernel the
    compiler refuses fails the run, it is never hidden behind the twin.
    The retry is safe for injected faults because the dispatch wrappers in
    `kernels/*/ops.py` raise BEFORE the compiled call touches its donated
    buffers; a genuine mid-execution failure may have consumed them, in
    which case the retry surfaces that error instead of masking it."""
    fn = build(arena.use_kernel)
    fn.compile(*args)
    try:
        return fn(*args)
    except Exception as e:
        if not arena.use_kernel:
            raise
        faults.DEGRADATIONS.record(site, e)
        log.warning("kernel dispatch %s failed; retrying on the jnp twin: "
                    "%r", site, e)
        arena.use_kernel = False
        return build(False)(*args)


class ResidentBitmapArena:
    """Persistent device copy of one workspace chunk's packed bitmaps."""

    def __init__(self, bits_u32: np.ndarray, alive: np.ndarray, *,
                 top_j: int = 16, mesh=None, use_kernel=None,
                 interpret=None, counter=TRANSFER):
        jax = _jax()
        from repro.kernels.common import (default_interpret,
                                          default_use_kernel, pow2)

        B, G, W = bits_u32.shape
        self.counter = counter
        self.G = int(G)
        self.J = max(1, min(int(top_j), G - 1))
        self.use_kernel = (default_use_kernel() if use_kernel is None
                           else bool(use_kernel))
        self.interpret = (default_interpret() if interpret is None
                          else bool(interpret))
        if mesh is not None:
            from repro.launch.mesh import dp_axes_of
            axes = dp_axes_of(mesh)
            n_shards = int(np.prod([mesh.shape[a] for a in axes]))
            if n_shards <= 1:  # a 1-device mesh shards nothing: skip the
                mesh = None    # shard_map layer, compile the plain jit
        if mesh is not None:
            self.axes = axes
        else:
            self.axes = ("data",)
            n_shards = 1
        self.mesh = mesh
        # pad W to a pow2 and B to a pow2 multiple of the shard count so the
        # per-shape jit caches stay small; padded rows are dead and all-zero
        self.B = int(B)
        self.Bp = n_shards * pow2(-(-B // n_shards), floor=1)
        self.Wp = pow2(int(W), floor=2)
        bits_p = np.zeros((self.Bp, G, self.Wp), dtype=np.uint32)
        bits_p[:B, :, :W] = bits_u32
        alive_p = np.zeros((self.Bp, G), dtype=np.int8)  # 1 byte/row on the wire
        alive_p[:B] = np.asarray(alive, dtype=bool)
        self._put = self._sharder(jax)
        self._bits = self._put(bits_p)
        self._alive = self._put(alive_p)
        counter.add_h2d(bits_p.nbytes + alive_p.nbytes, phase="upload")
        self.rounds = 0
        self._K = 0            # padded dirty-row count of the round op
        self.Rp = 0            # set by attach_counts
        self._counts = None    # v2 resident count state, or None (v1 mode)

    @classmethod
    def from_workspace(cls, ws, *, top_j: int = 16, mesh=None,
                       use_kernel=None, interpret=None, counter=TRANSFER,
                       with_counts: bool = True):
        """Upload a `BatchedGroupWorkspace` chunk's bitmaps (uint32 view of
        its uint64 words — bit positions follow the uint32 layout), and —
        unless ``with_counts=False`` — its exact integer count tensors, so
        the whole sweep runs against resident state."""
        bits = ws.bits.view(np.uint32)
        arena = cls(bits, ws.alive, top_j=top_j, mesh=mesh,
                    use_kernel=use_kernel, interpret=interpret,
                    counter=counter)
        if with_counts:
            arena.attach_counts(ws.CNT, ws.colsize, ws.memcol, ws.s,
                                ws.selfc, ws.nd, ws.hgt, ws.cost_row,
                                ws.alive)
        return arena

    def attach_counts(self, CNT, colsize, memcol, s, selfc, nd, hgt, cost,
                      alive):
        """Upload the integer count state (all values int32-guarded by the
        workspace build). The dirty queue starts as the alive mask —
        exactly the host sweep's initial queue."""
        from repro.kernels.common import pow2

        B, G, R = CNT.shape
        self.Rp = pow2(int(R), floor=8)
        cnt_p = np.zeros((self.Bp, G, self.Rp), dtype=np.int32)
        cnt_p[:B, :, :R] = CNT
        colsize_p = np.zeros((self.Bp, self.Rp), dtype=np.int32)
        colsize_p[:B, :R] = colsize
        # padded groups are all-dead: their zero state is inert in every op
        per_g = [np.zeros((self.Bp, G), dtype=np.int32) for _ in range(6)]
        for arr, src in zip(per_g, (memcol, s, selfc, nd, hgt, cost)):
            arr[:B] = src
        dirty_p = np.zeros((self.Bp, G), dtype=np.int8)
        dirty_p[:B] = np.asarray(alive, dtype=bool)
        self._CNT = self._put(cnt_p)
        self._colsize = self._put(colsize_p)
        (self._memcol, self._s, self._selfc, self._nd, self._hgt,
         self._cost) = [self._put(a) for a in per_g]
        self._dirty = self._put(dirty_p)
        self._counts = True
        self.counter.add_h2d(cnt_p.nbytes + colsize_p.nbytes +
                             sum(a.nbytes for a in per_g) + dirty_p.nbytes,
                             phase="upload")

    @classmethod
    def from_bank(cls, bank, ws, res_map, *, top_j: int = 16,
                  use_kernel=None, interpret=None, counter=TRANSFER):
        """Build a chunk arena by on-device EXTRACTION from the resident
        adjacency bank (ISSUE 9) — no bitmap/count upload at all.

        ``ws`` is a shape-only shell workspace (`BatchedGroupWorkspace`
        built with ``shell=True``): only its member layout (``members``,
        ``B``, ``G``, ``R``) is read; the big tensors never exist on host.
        The only h2d traffic is the (Bp, G) member/ptr/len index slab
        (phase ``extract``). Bank arrays are read without donation, so
        concurrent chunk thunks may extract from one bank. The extracted
        state is bit-identical to `from_workspace` of a fully host-packed
        chunk (test-enforced).
        """
        jax = _jax()
        import jax.numpy as jnp
        from repro.kernels.bitset_fold.ops import extract_fn
        from repro.kernels.common import (default_interpret,
                                          default_use_kernel, pow2)

        faults.check("resident.bank.extract")
        arena = cls.__new__(cls)
        B, G, R = int(ws.B), int(ws.G), int(ws.R)
        arena.counter = counter
        arena.G = G
        arena.J = max(1, min(int(top_j), G - 1))
        arena.use_kernel = (default_use_kernel() if use_kernel is None
                            else bool(use_kernel))
        arena.interpret = (default_interpret() if interpret is None
                           else bool(interpret))
        arena.mesh = None
        arena.axes = ("data",)
        arena.B = B
        arena.Bp = pow2(B, floor=1)
        arena.Wp = pow2(2 * max((R + 63) // 64, 1), floor=2)
        arena.Rp = pow2(R, floor=8)
        arena._put = arena._sharder(jax)
        members = np.full((arena.Bp, G), -1, dtype=np.int32)
        members[:B] = ws.members
        live = ws.members >= 0
        mem_c = np.where(live, ws.members, 0)
        ptr = np.zeros((arena.Bp, G), dtype=np.int32)
        lens = np.zeros((arena.Bp, G), dtype=np.int32)
        ptr[:B] = np.where(live, bank.ptr_host[mem_c], 0)
        lens[:B] = np.where(live, bank.len_host[mem_c], 0)
        Lp = pow2(int(lens.sum(axis=1).max()), floor=64)
        fn = extract_fn(arena.Bp, G, arena.Rp, arena.Wp, Lp, bank.cap,
                        int(bank._gids.shape[0]))
        counter.add_h2d(members.nbytes + ptr.nbytes + lens.nbytes,
                        phase="extract")
        (arena._bits, arena._alive, arena._dirty, arena._CNT,
         arena._colsize, arena._memcol, arena._s, arena._selfc, arena._nd,
         arena._hgt, arena._cost) = fn(
            bank._gids, bank._cnts, bank._size, bank._selfc, bank._nd,
            bank._hgt, res_map, jnp.asarray(members), jnp.asarray(ptr),
            jnp.asarray(lens))
        arena.rounds = 0
        arena._K = 0
        arena._counts = True
        return arena

    # ------------------------------------------------------------- plumbing
    def _sharder(self, jax):
        if self.mesh is None:
            import jax.numpy as jnp
            return jnp.asarray
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = P(self.axes if len(self.axes) > 1 else self.axes[0])
        sh = NamedSharding(self.mesh, spec)
        return lambda arr: jax.device_put(arr, sh)

    def _replicate(self, arr):
        if self.mesh is None:
            import jax.numpy as jnp
            return jnp.asarray(arr)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(arr, NamedSharding(self.mesh, P()))

    # ------------------------------------------------------------ round ops
    def topj_rows(self, rb: np.ndarray, rr: np.ndarray) -> np.ndarray:
        """Ranked top-J candidate columns of rows (rb[i], rr[i]) — one fused
        device ranking over the resident bitmaps; (n, J) int64 comes back."""
        from repro.kernels.bitset_fold.ops import topj_fn
        from repro.kernels.common import pow2

        n = rb.size
        # floor 64 keeps the per-shape jit cache tiny: late rounds all land
        # on one shape, and 64 padded rows cost ~J·64 wasted bytes at most
        n_pad = pow2(n, floor=64)
        rows = np.zeros((n_pad, 2), dtype=np.int32)
        rows[:n, 0] = rb
        rows[:n, 1] = rr

        def build(uk):
            return topj_fn(self.Bp, self.G, self.Wp, self.J, n_pad,
                           use_kernel=uk, interpret=self.interpret,
                           mesh=self.mesh, axes=self.axes)
        self.counter.add_h2d(rows.nbytes, phase="rank")
        out = np.asarray(_run_round_op(
            self, "kernel.bitset_fold.topj", build,
            (self._bits, self._alive, self._replicate(rows))))
        self.counter.add_d2h(out.nbytes, phase="rank")
        self.counter.tick_round()
        self.rounds += 1
        return out[:n].astype(np.int64)

    def fold(self, b: np.ndarray, a: np.ndarray, z: np.ndarray,
             ca: np.ndarray, cz: np.ndarray):
        """Fold one round's accepted pairs (rows z into rows a of groups b,
        member columns ca/cz) into the resident bitmaps, in place."""
        from repro.kernels.bitset_fold.ops import fold_fn
        from repro.kernels.common import pow2

        m = b.size
        if m == 0:
            return
        # slot of each pair within its group (b arrives sorted ascending)
        head = np.concatenate([[True], b[1:] != b[:-1]])
        starts = np.flatnonzero(head)
        counts = np.diff(np.concatenate([starts, [m]]))
        slot = np.arange(m) - np.repeat(starts, counts)
        P_pairs = min(pow2(int(counts.max()), floor=2), max(self.G // 2, 1))
        # int16 on the wire when it provably fits (rows < G ≤ 128; word
        # indices < Wp ≤ 2^13); a wide column universe widens to int32
        # instead of truncating — the device casts to int32 either way
        dtype = np.int16 if self.Wp <= (1 << 13) else np.int32
        instr = np.zeros((self.Bp, P_pairs, 8), dtype=dtype)
        instr[b, slot, 0] = a
        instr[b, slot, 1] = z
        instr[b, slot, 2] = ca >> 5
        instr[b, slot, 3] = ca & 31
        instr[b, slot, 4] = cz >> 5
        instr[b, slot, 5] = cz & 31
        instr[b, slot, 6] = 1

        def build(uk):
            return fold_fn(self.Bp, self.G, self.Wp, P_pairs,
                           use_kernel=uk, interpret=self.interpret,
                           mesh=self.mesh, axes=self.axes)
        self.counter.add_h2d(instr.nbytes, phase="fold")
        self._bits, self._alive = _run_round_op(
            self, "kernel.bitset_fold.fold", build,
            (self._bits, self._alive, self._put(instr)))

    # ----------------------------------------- v2: whole-iteration residency
    def _state(self):
        return (self._bits, self._alive, self._dirty, self._CNT,
                self._colsize, self._memcol, self._s, self._selfc, self._nd,
                self._hgt, self._cost)

    def propose_rows(self, rb: np.ndarray, rr: np.ndarray, j_max: int,
                     theta_p: int, height_bound):
        """One fused proposal round over the resident state.

        ``rb``/``rr`` are the HOST's dirty rows — the device never sees
        them (it derives the identical list from its resident ``dirty``
        mirror); they only size the padded row count and order the returned
        verdicts. Returns ``(accept, partner)`` bool/(int64) arrays of
        length ``rb.size``. ``j_max`` is ignored for compilation (the op
        always traces J = top_j and masks per-row, so every round of an
        iteration hits one executable).
        """
        import jax.numpy as jnp
        from repro.kernels.bitset_fold.ops import round_fn
        from repro.kernels.common import pow2

        if self._counts is None:
            raise RuntimeError("propose_rows needs attach_counts state")
        n = rb.size
        # the dirty queue only shrinks, so the first round's padded count
        # holds for the arena's life: one compiled round op per chunk
        self._K = max(self._K, pow2(n, floor=64))

        def build(uk):
            return round_fn(self.Bp, self.G, self.Rp, self.Wp, self._K,
                            self.J, self.J, height_bound=height_bound,
                            use_kernel=uk, interpret=self.interpret,
                            mesh=self.mesh, axes=self.axes)
        self.counter.add_h2d(4, phase="rank")  # the θ̂ scalar
        self._dirty, out = _run_round_op(
            self, "kernel.bitset_fold.round", build,
            self._state() + (jnp.uint32(theta_p),))
        out = np.asarray(out)
        self.counter.add_d2h(out.nbytes, phase="rank")
        self.counter.tick_round()
        self.rounds += 1
        if self.mesh is not None:
            out = out[rb, rr]          # (B, G, 2) → host-side dirty gather
        else:
            out = out[:n]
        return out[:, 0] > 0, out[:, 1].astype(np.int64)

    def fold_counts(self, b: np.ndarray, a: np.ndarray, z: np.ndarray):
        """Fold one round's accepted pairs (rows z into rows a of groups b)
        into the WHOLE resident state, in place. Member columns come from
        the resident ``memcol`` — the instruction slab is 12 bytes/pair."""
        from repro.kernels.bitset_fold.ops import fold_counts_fn
        from repro.kernels.common import pow2

        if self._counts is None:
            raise RuntimeError("fold_counts needs attach_counts state")
        m = b.size
        if m == 0:
            return
        # slot of each pair within its group (b arrives sorted ascending)
        head = np.concatenate([[True], b[1:] != b[:-1]])
        starts = np.flatnonzero(head)
        counts = np.diff(np.concatenate([starts, [m]]))
        slot = np.arange(m) - np.repeat(starts, counts)
        # a matching holds at most G // 2 pairs per group; padding every
        # round to that keeps one compiled fold op per chunk
        P_pairs = max(self.G // 2, 1)
        instr = np.zeros((self.Bp, P_pairs, 3), dtype=np.int32)
        instr[b, slot, 0] = a
        instr[b, slot, 1] = z
        instr[b, slot, 2] = 1

        def build(uk):
            return fold_counts_fn(self.Bp, self.G, self.Rp, self.Wp,
                                  P_pairs, use_kernel=uk,
                                  interpret=self.interpret, mesh=self.mesh,
                                  axes=self.axes)
        self.counter.add_h2d(instr.nbytes, phase="fold")
        (self._bits, self._alive, self._dirty, self._CNT, self._colsize,
         self._s, self._selfc, self._nd, self._hgt,
         self._cost) = _run_round_op(
            self, "kernel.bitset_fold.fold_counts", build,
            self._state() + (self._put(instr),))

    # --------------------------------------------------- sync-back contract
    def sync_rows(self, b: np.ndarray, g: np.ndarray) -> np.ndarray:
        """Download selected (dirty) bitmap rows — (n, Wp) uint32. The
        verification hook of DESIGN.md §9: callers compare these against the
        host fold; the engine itself never needs them (Savings run on the
        host-resident count tensors)."""
        rows = np.asarray(self._bits)[np.asarray(b), np.asarray(g)]
        self.counter.add_d2h(rows.nbytes, phase="sync")
        return rows

    def host_bits(self) -> np.ndarray:
        """Full (B, G, Wp) download (tests/debug only — counts as d2h)."""
        out = np.asarray(self._bits)[: self.B]
        self.counter.add_d2h(out.nbytes, phase="sync")
        return out

    def host_alive(self) -> np.ndarray:
        out = np.asarray(self._alive)[: self.B] > 0
        self.counter.add_d2h(out.nbytes, phase="sync")
        return out

    def host_counts(self):
        """Download the resident count state — ``(CNT, colsize, memcol, s,
        selfc, nd, hgt, cost)`` host copies trimmed to the live batch rows.
        Verification contract only (phase ``sync``): tests compare these
        against a host `_fill` of the same chunk."""
        if self._counts is None:
            raise RuntimeError("host_counts needs attach_counts state")
        arrs = [np.asarray(a) for a in
                (self._CNT, self._colsize, self._memcol, self._s,
                 self._selfc, self._nd, self._hgt, self._cost)]
        self.counter.add_d2h(sum(a.nbytes for a in arrs), phase="sync")
        return tuple(a[: self.B] for a in arrs)


class ResidentAdjacencyBank:
    """Per-root adjacency rows carried ON DEVICE across iterations (§9).

    Append-only ``gid``/``cnt`` int32 streams (pow2-grown, donated across
    advances) hold every root's coalesced external adjacency row exactly as
    `SluggerState` would materialize it at the root's mint time: entries are
    ``(gid, cnt)`` with gids resolved to roots AS OF that mint (stored ids
    go stale as neighbours merge — extraction re-resolves them through the
    current ``res_map`` and re-coalesces, which is precisely the host's
    `gather_rows` resolve+coalesce). Four (cap,) stat arrays mirror
    ``size``/``selfcnt``/``ndesc``/``height``. The HOST keeps only the
    integer row directory (``ptr_host``/``len_host``/``top``) — row
    lengths are known host-side because `merge_batch` computes the same
    ``row_len`` and the engine forwards it with each applied batch.

    Exactness guard: merges only coalesce counts (sum-preserving) or drop
    internal pairs, so Σcnt never exceeds the seed edge count ``m``; every
    extracted CNT value is ≤ m and every clamped integer row cost is
    ≤ 3m/2 + 2n + 16. The constructor refuses (OverflowError) any graph
    where that bound reaches C_CLAMP — callers fall back to the
    host-rebuilt path, whose `_fill` re-checks per chunk at runtime — so
    ON the bank path all device int32 cost arithmetic is provably exact
    and extraction needs no overflow checks (and no downloads at all).
    """

    def __init__(self, g, *, counter=TRANSFER, min_capacity: int = 0):
        _jax()
        import jax.numpy as jnp
        from repro.core.merging import C_CLAMP
        from repro.kernels.common import pow2

        self.counter = counter
        self.n = int(g.n)
        self.cap = 2 * self.n + 8
        indices = np.asarray(g.indices)
        m = int(indices.size)
        if (3 * m) // 2 + 2 * self.n + 16 >= C_CLAMP:
            raise OverflowError(
                "graph too heavy for the int32 adjacency bank: the "
                "conservation bound 3m/2 + 2n + 16 reaches C_CLAMP")
        E0 = pow2(max(2 * m, int(min_capacity), 64))
        gids = np.zeros(E0, dtype=np.int32)
        gids[:m] = indices
        cnts = np.zeros(E0, dtype=np.int32)
        cnts[:m] = 1
        self.ptr_host = np.zeros(self.cap, dtype=np.int64)
        self.len_host = np.zeros(self.cap, dtype=np.int64)
        self.ptr_host[: self.n] = g.indptr[:-1]
        self.len_host[: self.n] = np.diff(g.indptr)
        self.top = m
        self._gids = jnp.asarray(gids)
        self._cnts = jnp.asarray(cnts)
        # stats live on device from the start — zero h2d for them
        self._size = jnp.ones(self.cap, dtype=jnp.int32)
        self._selfc = jnp.zeros(self.cap, dtype=jnp.int32)
        self._nd = jnp.zeros(self.cap, dtype=jnp.int32)
        self._hgt = jnp.zeros(self.cap, dtype=jnp.int32)
        counter.add_h2d(gids.nbytes + cnts.nbytes, phase="init")

    @property
    def capacity(self) -> int:
        return int(self._gids.shape[0])

    def advance_batches(self, res_map, batches: list):
        """Advance the bank by one iteration's applied merge batches.

        ``batches`` is a list of ``(A, Z, M, lens)`` — the exact arrays the
        engine captured at `apply_plans`'s ``on_batch`` hook, with ``lens ==
        state.row_len[M]`` read at that instant (the freshly minted rows'
        unique-external counts). Batches are replayed SEQUENTIALLY so each
        device batch resolves gids through the same pre-batch root map the
        host `merge_batch` used; ``res_map`` is threaded through and
        returned. Per batch the only upload is the (8, Pp) i32 instruction
        slab (32 B/pair, phase ``bank``); regrows are device-to-device.
        """
        import jax.numpy as jnp
        from repro.kernels.bitset_fold.carry import (bank_advance_fn,
                                                     bank_grow_fn)
        from repro.kernels.common import pow2

        # checked BEFORE any host directory mutation: a fault here leaves
        # the bank untouched, so the engine's advance degradation can just
        # drop the run context without unwinding partial state
        faults.check("resident.bank.advance")
        for A, Z, M, lens in batches:
            m = int(A.size)
            if m == 0:
                continue
            ub = self.len_host[A] + self.len_host[Z]
            tot = int(ub.sum())
            need = self.top + tot
            E = self.capacity
            if need > E:
                newE = pow2(max(need, 2 * E))
                if newE >= (1 << 31):
                    raise OverflowError(
                        "adjacency bank outgrew int32 addressing")
                self._gids, self._cnts = bank_grow_fn(E, newE)(
                    self._gids, self._cnts)
                E = newE
            Pp = pow2(m, floor=64)
            Tp = pow2(max(tot, 1), floor=256)
            outp = self.top + np.cumsum(ub) - ub
            slab = np.zeros((8, Pp), dtype=np.int32)
            slab[0] = self.cap          # pads: ids scatter-drop at cap,
            slab[1] = self.cap          # out_ptr drops at E, lengths 0
            slab[2] = self.cap
            slab[3] = E
            slab[0, :m] = A
            slab[1, :m] = Z
            slab[2, :m] = M
            slab[3, :m] = outp
            slab[4, :m] = self.ptr_host[A]
            slab[5, :m] = self.len_host[A]
            slab[6, :m] = self.ptr_host[Z]
            slab[7, :m] = self.len_host[Z]
            fn = bank_advance_fn(self.cap, E, Pp, Tp)
            self.counter.add_h2d(slab.nbytes, phase="bank")
            (self._gids, self._cnts, self._size, self._selfc, self._nd,
             self._hgt, res_map) = fn(self._gids, self._cnts, self._size,
                                      self._selfc, self._nd, self._hgt,
                                      res_map, jnp.asarray(slab))
            self.ptr_host[M] = outp
            self.len_host[M] = lens
            self.len_host[A] = 0       # consumed roots own no row anymore
            self.len_host[Z] = 0
            self.top = need
        return res_map

    # --------------------------------------------------- sync-back contract
    def host_rows(self, roots, res_map):
        """Materialize the CURRENT coalesced adjacency rows of ``roots`` on
        host — the bank's verification contract (phase ``sync``): resolve
        each stored gid through ``res_map`` and re-coalesce, exactly like
        `SluggerState.gather_rows`. Returns a list of ``(nbr, cnt)`` int64
        pairs sorted ascending by nbr. Tests/debug only."""
        gids = np.asarray(self._gids)
        cnts = np.asarray(self._cnts)
        rm = np.asarray(res_map)
        self.counter.add_d2h(gids.nbytes + cnts.nbytes + rm.nbytes,
                             phase="sync")
        out = []
        for r in np.asarray(roots, dtype=np.int64):
            p = int(self.ptr_host[r])
            l = int(self.len_host[r])
            rg = rm[gids[p:p + l]]
            c = cnts[p:p + l]
            order = np.argsort(rg, kind="stable")
            rg = rg[order]
            c = c[order]
            if l:
                head = np.concatenate([[True], rg[1:] != rg[:-1]])
                idx = np.flatnonzero(head)
                out.append((rg[idx].astype(np.int64),
                            np.add.reduceat(c, idx).astype(np.int64)))
            else:
                out.append((np.zeros(0, np.int64), np.zeros(0, np.int64)))
        return out


class ResidentRunContext:
    """Per-run device state of the single-device resident backend.

    Holds what outlives one iteration (the arenas are per-iteration,
    per-chunk):

    * the STATIC edge arrays, uploaded once per run (phase ``init``) —
      candidate generation's O(|E|) hashing never re-ships the graph;
    * ``res_map`` (cap,) int32 — the current root of every arena id,
      advanced at every exchange stage by replaying the applied merge
      plans (`merging.apply_plans`'s ``on_batch`` hook feeds the exact
      (A, Z, M) batches): a forward map with the iteration's merges is
      built on device and collapsed by pointer doubling (2^16 covers any
      in-iteration merge chain), then composed into ``res_map``. Per
      iteration only the ~12 bytes/merge instruction stream crosses up
      (phase ``carry``) — the map itself never leaves the device.

    ``for_roots`` is the engine's shingle-provider hook: root shingles
    compute ON DEVICE from the resident edges and ``res_map`` (tentpole 3
    of ISSUE 7 — resident candidate generation); per rehash only the
    (n_ids,) shingle vector and the per-root leaf counts come back (phase
    ``candgen``). The results are bit-identical to the host u32 twin
    (`minhash.host_shingle_provider`) and the mesh shard_map path.

    With ``bank=True`` the context additionally carries a
    `ResidentAdjacencyBank` (ISSUE 9) — the device-resident row arena
    that `ResidentBitmapArena.from_bank` extracts next-iteration
    workspaces from, making host workspaces shape-only shells. In bank
    mode `advance` expects the engine's 4-tuple ``(A, Z, M, lens)``
    batches and the plan-replay ``carry`` upload is superseded: the
    bank-advance slab already names (A, Z, M), so ``res_map`` composes
    inside the same donated device call. If the bank's exactness guard
    declines the graph (`OverflowError` at seed time), ``bank`` stays
    ``None``, the engine falls back to the host-rebuilt upload path, and
    the fallback is recorded in `faults.DEGRADATIONS`.
    """

    def __init__(self, g, *, counter=TRANSFER, bank: bool = False,
                 bank_min_capacity: int = 0):
        _jax()
        import jax.numpy as jnp

        self.counter = counter
        self.n = int(g.n)
        self.cap = 2 * self.n + 8      # SluggerState's id capacity
        src = np.repeat(np.arange(g.n), np.diff(g.indptr)).astype(np.int32)
        dst = np.asarray(g.indices, dtype=np.int32)
        self._src = jnp.asarray(src)
        self._dst = jnp.asarray(dst)
        self._res_map = jnp.arange(self.cap, dtype=jnp.int32)
        counter.add_h2d(src.nbytes + dst.nbytes, phase="init")
        self.bank = None
        if bank:
            try:
                self.bank = ResidentAdjacencyBank(
                    g, counter=counter, min_capacity=bank_min_capacity)
            except OverflowError as e:
                # exactness guard tripped — stay on the host-rebuilt path
                # (its per-chunk `_fill` guards re-check at runtime), and
                # count it: the run is no longer the bank path
                faults.DEGRADATIONS.record("resident.bank.init", e)
                log.warning("adjacency bank declined the graph; using the "
                            "host-rebuilt upload path: %r", e)

    # ------------------------------------------------------- plan replay
    def advance(self, batches: list):
        """Replay one iteration's applied merge batches against the
        resident root map — and, when the adjacency bank is live, against
        the bank itself.

        Legacy (bank-less) mode takes ``(A, Z, M)`` global id triples in
        application order and composes them in ONE device call. Bank mode
        requires ``(A, Z, M, lens)`` 4-tuples (``lens = state.row_len[M]``
        captured at the ``on_batch`` hook) and replays them sequentially —
        each bank batch must see the pre-batch root map, exactly like the
        host `merge_batch`.
        """
        import jax.numpy as jnp
        from repro.kernels.bitset_fold.carry import advance_fn
        from repro.kernels.common import pow2

        if self.bank is not None:
            if any(len(b) < 4 for b in batches):
                raise ValueError(
                    "bank carry needs (A, Z, M, lens) batches — pass "
                    "state.row_len[M] captured at the on_batch hook")
            self._res_map = self.bank.advance_batches(self._res_map,
                                                      batches)
            return
        m = sum(b[0].size for b in batches)
        if m == 0:
            return
        mp = pow2(m, floor=64)
        tri = np.full((3, mp), self.cap, dtype=np.int32)  # pads scatter-drop
        tri[0, :m] = np.concatenate([b[0] for b in batches])
        tri[1, :m] = np.concatenate([b[1] for b in batches])
        tri[2, :m] = np.concatenate([b[2] for b in batches])
        fn = advance_fn(self.cap, mp)
        self.counter.add_h2d(tri.nbytes, phase="carry")
        self._res_map = fn(self._res_map, jnp.asarray(tri))

    def root_of_host(self) -> np.ndarray:
        """Download res_map[:n] (tests/debug — the verification contract
        against `SluggerState.root_of`; the engine never calls this)."""
        out = np.asarray(self._res_map)[: self.n].astype(np.int64)
        self.counter.add_d2h(out.nbytes, phase="sync")
        return out

    # ----------------------------------------------- resident candidate gen
    def for_roots(self, root_of: np.ndarray):
        """Shingle-provider hook (`minhash.candidate_groups` protocol).

        ``root_of`` (the host map) is intentionally unused: the resident
        ``res_map`` IS that mapping — `advance` replayed every applied
        plan — so the roots come from device state and only the per-root
        results cross the boundary.
        """
        import jax.numpy as jnp
        from repro.kernels.bitset_fold.carry import shingle_roots_fn
        from repro.core.minhash import u32_seed_consts

        fn = shingle_roots_fn(self.n, self.cap, self._src.shape[0])

        def shingle_fn(sub_seed: int, n_ids: int) -> np.ndarray:
            a, b = u32_seed_consts(sub_seed)
            sh, cnt = fn(self._src, self._dst, self._res_map,
                         jnp.uint32(a), jnp.uint32(b))
            sh = np.asarray(sh)
            cnt = np.asarray(cnt)
            self.counter.add_d2h(sh.nbytes + cnt.nbytes, phase="candgen")
            out = sh.astype(np.int64)[:n_ids]
            # leafless ids take the unique sentinel 2^32 + id — the same
            # rule as `minhash.rootwise_min(…, sentinel_base=1 << 32)`
            missing = np.flatnonzero(cnt[:n_ids] == 0)
            out[missing] = (1 << 32) + missing
            return out

        return shingle_fn
