"""Partition-parallel, stage-based summarization engine (DESIGN.md §8).

`SummarizerEngine` is the driver behind `slugger.summarize()`: the old
monolithic per-iteration loop broken into five explicit, pluggable stages

    shingle → group → pack → merge_round → exchange

run T times over a `PartitionedGraph`, followed by partition-aware emission
and pruning. Candidate generation is global (shingles and groups are cheap,
O(|E|) array passes); candidate GROUPS — where the quadratic in-group work
lives — are assigned to partitions by node ownership and swept shard-local
in record mode (`merging.MergePlan`), so the only data crossing a partition
boundary between rounds is the exchange stage's replay of forward/root
pointer updates (`merging.apply_plans`).

Determinism is the load-bearing property: every stage is either global and
seeded (shingle/group), a pure function of one group's snapshot tensors and
its own spawned RNG stream (merge_round), or a canonical-order replay
(exchange). Consequently ``partitions=k`` produces BIT-IDENTICAL summaries
to ``partitions=1`` for every backend and any thread schedule —
test-enforced in `tests/test_engine_partitioned.py`.

Per-iteration randomness comes from `np.random.SeedSequence(seed).spawn(T)`
— no arithmetic on raw seeds anywhere, so distinct (seed, iteration, group)
triples can never alias (the old ``seed * 7919 + t`` did: seed=0,t=7919 ≡
seed=1,t=0).

``backend="numpy"`` is the exact host oracle (``"loop"`` its
paper-faithful sequential variant). ``backend="resident"`` is the device
path: each workspace chunk's bitmaps live in a
`core/resident.ResidentBitmapArena` and every merge round runs as on-device
fused top-J ranking + bitset-OR folds, with only tiny plans crossing the
host↔device boundary (DESIGN.md §9). With more than one device visible (or
a mesh passed explicitly) its shingles run as `core/distributed`'s sharded
program and its arenas shard over the mesh.
"""
from __future__ import annotations

import logging
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from repro import faults
from repro.core.merging import (_BATCH_MAX_GROUP, apply_plans,
                                build_merge_work)
from repro.core.minhash import candidate_groups
from repro.core.pruning import prune
from repro.core.slugger import SluggerState, _emit_encoding
from repro.core.spans import COUNTS, GLOBAL as SPANS, span
from repro.graphs.partitioned import PartitionedGraph, as_partitioned

log = logging.getLogger("repro.engine")

STAGE_ORDER = ("shingle", "group", "pack", "merge_round", "exchange")

# flat float entries of ``SummarizerEngine.stats`` read from one job's spans
# (`core/spans.py`): stats key -> (span name, field of its totals)
SPAN_STATS = {
    **{name: (f"stage.{name}", "wall") for name in STAGE_ORDER},
    "setup": ("setup", "wall"),
    "checkpoint": ("checkpoint", "wall"),
    "exchange.replay": ("exchange.replay", "wall"),
    "exchange.bank_advance": ("exchange.bank_advance", "wall"),
    "merge.host_sweep": ("merge.host_sweep", "wall"),
    "merge.device_sweep": ("merge.device_sweep", "wall"),
    "merge.extract": ("merge.extract", "wall"),
    "merge.round": ("merge.round", "wall"),
    "merge.fold": ("merge.fold", "wall"),
    "merge.chunk.self": ("merge.chunk", "self"),
    "merge.thunk": ("merge.thunk", "wall"),
    "merge.thunk.max": ("merge.thunk", "max"),
    "merge.thunk.cpu": ("merge.thunk", "cpu"),
    # what a mesh run adds (0.0 on one device): each rehash's sharded
    # shingles, the arenas' sharded uploads, and the host fill of a dense
    # workspace chunk in pack (a shell chunk of the bank path has none)
    "mesh.shingle": ("mesh.shingle", "wall"),
    "mesh.upload": ("mesh.upload", "wall"),
    "pack.fill": ("pack.fill", "wall"),
}

# flat entries of ``SummarizerEngine.stats`` read from one job's tallies
# (`spans.COUNTS`), as floats like the spans' seconds: the mesh-sharded
# arenas, their rows real and padded to a multiple of the shard count, and
# the devices holding a shard of each, summed over arenas; and the devices
# that swept at least one group over 128 members, summed over iterations
# (all 0.0 on one device)
COUNT_STATS = ("mesh.arenas", "mesh.rows", "mesh.rows_padded",
               "mesh.shard_devices", "mesh.sweep_devices")

# the same, over emission and pruning (`run`): the pair lists they ordered
# or searched by the packed int64 key, and those that fell back to the
# multi-key lexsort (`summary_ir.pair_key`)
POST_COUNT_STATS = ("post.sorts", "post.sort_fallbacks")


class IterationContext:
    """Mutable scratch shared by one iteration's stages."""

    __slots__ = ("t", "theta", "state", "pg", "ss_groups", "ss_merge",
                 "shingle_fn", "groups", "group_children", "group_seeds",
                 "plans", "thunks", "merges", "sweep_chips")

    def __init__(self, t: int, theta: float, state, pg):
        self.t = t
        self.theta = theta
        self.state = state
        self.pg = pg
        self.shingle_fn = None
        self.groups = []
        self.group_children = []
        self.group_seeds = np.zeros(0, dtype=np.uint64)
        self.plans = []
        self.thunks = []
        self.merges = 0
        self.sweep_chips = set()  # devices given a device sweep in pack


class SummarizerEngine:
    """Configured, reusable SLUGGER driver.

    Parameters mirror `summarize()` plus:

    * ``partitions`` — number of node-ownership shards; ``1`` is the
      monolithic special case and the semantics never depend on the value.
    * ``workers`` — threads for the merge_round stage (record-mode sweeps
      are pure local array work, so they parallelize safely). Defaults to
      ``min(partitions, cpu count)``.
    * ``mesh`` — a jax mesh for ``backend="resident"``: sharded shingles
      and arena placement. ``None`` auto-enables when more than one device
      is visible.
    * ``stages`` — dict overriding any of the five stage callables (each
      called as ``fn(engine, ctx)``).
    """

    def __init__(self, partitions: int = 1, backend: str = "numpy",
                 T: int = 20, seed: int = 0, max_group: int = 500,
                 top_j: int = 16, height_bound=None, prune_steps=(1, 2, 3),
                 workers: int | None = None, mesh=None, stages: dict | None = None):
        if backend not in ("numpy", "loop", "resident"):
            raise ValueError(
                f"unknown backend {backend!r}; use 'numpy', 'resident' or "
                f"'loop'")
        if partitions < 1:
            raise ValueError("partitions must be >= 1")
        self.partitions = int(partitions)
        self.backend = backend
        self.T = int(T)
        self.seed = seed
        self.max_group = max_group
        self.top_j = top_j
        self.height_bound = height_bound
        self.prune_steps = tuple(prune_steps)
        self.workers = (min(self.partitions, os.cpu_count() or 1)
                        if workers is None else max(1, int(workers)))
        self.mesh = mesh
        self.stages = {name: getattr(type(self), f"stage_{name}")
                       for name in STAGE_ORDER}
        if stages:
            unknown = set(stages) - set(STAGE_ORDER)
            if unknown:
                raise ValueError(f"unknown stages {sorted(unknown)}; "
                                 f"valid: {STAGE_ORDER}")
            self.stages.update(stages)
        self.stats: dict = {}
        self._shingle_provider = None
        self._resident_factory = None
        self._run_ctx = None
        self._devices = 1
        self._sweep_devices = None

    # ------------------------------------------------------------- plumbing
    def _mesh_active(self):
        """Resolve the resident backend's mesh (or None). An explicit
        one-device mesh shards nothing: it selects the single-device path
        (run context, adjacency bank) on that device."""
        if self.backend != "resident":
            return None
        if self.mesh is not None:
            return self.mesh if self.mesh.size > 1 else None
        try:
            import jax
        except ImportError:  # jax not installed: host path
            return None
        if jax.device_count() > 1:
            from repro.launch.mesh import make_data_mesh
            return make_data_mesh()
        return None

    def _setup_dispatches(self, g):
        """Wire the resident device paths for this run."""
        self._shingle_provider = None
        self._resident_factory = None
        self._run_ctx = None
        self._sweep_devices = None
        mesh = self._mesh_active()
        self._devices = 1 if mesh is None else int(mesh.size)
        if self.backend == "resident":
            if mesh is not None:
                # no bank on a mesh: a group over 128 members is swept on
                # one of the mesh's devices from a host-filled arena
                self._sweep_devices = list(mesh.devices.flat)
            from repro.core.resident import ResidentBitmapArena

            def factory(ws, _mesh=mesh, _j=self.top_j):
                rc = self._run_ctx
                if _mesh is None and rc is not None and rc.bank is not None:
                    # bank path: the chunk state EXTRACTS on device from the
                    # resident adjacency bank — ws is a shape-only shell.
                    # Extraction failures surface as BankFault so the stage
                    # loop can degrade to host-rebuilt workspaces (§11) —
                    # the shell ws carries no tensors, so a plain retry
                    # against it would read garbage.
                    try:
                        return ResidentBitmapArena.from_bank(
                            rc.bank, ws, rc._res_map, top_j=_j)
                    except Exception as e:
                        raise faults.BankFault(
                            f"bank extract failed: {e!r}") from e
                return ResidentBitmapArena.from_workspace(ws, top_j=_j,
                                                          mesh=_mesh)
            self._resident_factory = factory
        if mesh is None:
            # Single device: every backend shingles with the unified u32
            # family so the cross-backend bit-identity contract covers
            # candidate generation. The resident backend computes them ON
            # DEVICE from its run context (edges uploaded once, root map
            # advanced by plan replay); the others use the NumPy twin.
            if self.backend == "resident":
                from repro.core.resident import ResidentRunContext
                self._run_ctx = ResidentRunContext(g, bank=True)
                self._shingle_provider = self._run_ctx.for_roots
            if self._shingle_provider is None:
                from repro.core.minhash import host_shingle_provider
                self._shingle_provider = host_shingle_provider(g)
            return
        from repro.core import distributed as D
        self._shingle_provider = D.shingle_provider(g, mesh)

    # --------------------------------------------------------------- stages
    def stage_shingle(self, ctx: IterationContext):
        """Prepare this iteration's shingle provider (host segment-min by
        default; the device run context on one device; the mesh-sharded
        program on a mesh). The provider is consumed by the group stage, which
        owns the rehash loop."""
        if self._shingle_provider is not None:
            ctx.shingle_fn = self._shingle_provider(ctx.state.root_of)

    def stage_group(self, ctx: IterationContext):
        """Global candidate generation + per-group RNG stream spawning."""
        state = ctx.state
        ctx.groups = candidate_groups(
            state.g, state.root_of, state.alive, seed=ctx.ss_groups,
            max_group=self.max_group, shingle_fn=ctx.shingle_fn)
        if ctx.groups:
            ctx.group_children = ctx.ss_merge.spawn(len(ctx.groups))
            ctx.group_seeds = np.array(
                [c.generate_state(1, dtype=np.uint64)[0]
                 for c in ctx.group_children], dtype=np.uint64)

    def stage_pack(self, ctx: IterationContext):
        """Assign groups to partitions by node ownership and build their
        record-mode workspaces against the iteration-start snapshot."""
        groups = ctx.groups
        ctx.plans = [None] * len(groups)
        ctx.thunks = []
        ctx.sweep_chips = set()
        if not groups:
            return
        part_of_group = self._group_partitions(ctx)
        shell = (self.backend == "resident" and self._run_ctx is not None
                 and getattr(self._run_ctx, "bank", None) is not None)
        sweep_dev = None
        if self._sweep_devices:
            # the groups over 128 members go round-robin, in group order,
            # to the mesh's devices: the same job places the same sweeps
            sweep_dev = [None] * len(groups)
            devs = self._sweep_devices
            over = [i for i, grp in enumerate(groups)
                    if len(grp) > _BATCH_MAX_GROUP]
            for n, i in enumerate(over):
                sweep_dev[i] = devs[n % len(devs)]
            ctx.sweep_chips = {sweep_dev[i] for i in over}
        for p in np.unique(part_of_group):
            idxs = np.flatnonzero(part_of_group == p)
            plans_p, thunks_p = build_merge_work(
                ctx.state, [groups[i] for i in idxs], ctx.theta,
                group_seeds=ctx.group_seeds[idxs],
                rng_of=lambda li, idxs=idxs: np.random.default_rng(
                    ctx.group_children[idxs[li]]),
                top_j=self.top_j, height_bound=self.height_bound,
                backend=self.backend,
                resident_factory=self._resident_factory,
                shell_workspaces=shell,
                sweep_devices=(None if sweep_dev is None
                               else [sweep_dev[i] for i in idxs]))
            for li, gi in enumerate(idxs):
                ctx.plans[int(gi)] = plans_p[li]
            ctx.thunks.extend(thunks_p)

    def stage_merge_round(self, ctx: IterationContext):
        """Run the shard-local sweeps — serial or thread-parallel; record
        mode makes the schedule irrelevant to the outcome. Each thunk opens
        its own ``merge.thunk`` span (`merging.build_merge_work`)."""
        if self.workers > 1 and len(ctx.thunks) > 1:
            with ThreadPoolExecutor(max_workers=self.workers) as pool:
                list(pool.map(lambda f: f(), ctx.thunks))
        else:
            for thunk in ctx.thunks:
                thunk()
        if ctx.sweep_chips:
            COUNTS.add("mesh.sweep_devices", len(ctx.sweep_chips))

    def stage_exchange(self, ctx: IterationContext):
        """Replay all recorded merge rounds against the global state in
        canonical group order — the only cross-partition communication.
        Under the single-device resident backend the applied (A, Z, M)
        batches also feed the run context, which replays them against its
        device root map (plan-driven carry — the map never re-uploads)."""
        ctx.merges = self._replay_plans(ctx.state, ctx.plans)

    def _replay_plans(self, state, plans: list) -> int:
        """Apply recorded plans to the global state — shared by the
        exchange stage and checkpoint-resume replay. A live resident run
        context rides along on the applied (A, Z, M) batches; if its bank
        advance fails the GLOBAL state is already correct (plans applied
        first), so the run degrades to the host workspace path and keeps
        going instead of crashing."""
        if self._run_ctx is not None:
            batches: list = []
            # row_len[M] is pristine exactly at the on_batch hook — the bank
            # carry needs the minted rows' unique-external counts
            with span("exchange.replay"):
                merges = apply_plans(
                    state, plans,
                    on_batch=lambda A, Z, M: batches.append(
                        (A, Z, M, state.row_len[M].copy())))
            try:
                with span("exchange.bank_advance"):
                    self._run_ctx.advance(batches)
            except Exception as e:
                self._degrade_to_host(state, "resident.bank.advance", e)
            return merges
        with span("exchange.replay"):
            return apply_plans(state, plans)

    def _degrade_to_host(self, state, site: str, exc) -> None:
        """§11 degradation policy: drop the device state that failed and
        finish the run on host workspaces and host sweeps — bit-identical
        by the unified-u32 shingle/ranking contract, just slower. On one
        device that is the resident run context (bank, device root map,
        device shingles); on a mesh, the device sweeps of the groups over
        128 members. Counted in ``stats["degradations"]`` via the global
        ledger."""
        faults.DEGRADATIONS.record(site, exc)
        log.warning("degrading to host workspace path after %s fault: %r",
                    site, exc)
        self._sweep_devices = None
        if self._run_ctx is not None:
            self._run_ctx = None
            from repro.core.minhash import host_shingle_provider
            self._shingle_provider = host_shingle_provider(state.g)

    def _group_partitions(self, ctx: IterationContext) -> np.ndarray:
        """Partition of each group = owner of its smallest member root's
        smallest leaf (`SluggerState.root_min_leaf`, the same keying the
        partition-aware emission uses; ownership keeps a root's groups
        co-resident with most of its adjacency)."""
        n_groups = len(ctx.groups)
        if self.partitions == 1:
            return np.zeros(n_groups, dtype=np.int64)
        min_leaf = ctx.state.root_min_leaf()
        key_roots = np.array([int(g.min()) for g in ctx.groups],
                             dtype=np.int64)
        return ctx.pg.owner[min_leaf[key_roots]]

    # ------------------------------------------------------------------ run
    def _config(self) -> dict:
        """JSON-safe config snapshot recorded in checkpoints. The
        DECISION_KEYS subset is resume-enforced; backend/partitions are
        informational — replay determinism makes checkpoints portable
        across both (test-enforced in tests/test_checkpoint_resume.py)."""
        height = self.height_bound
        return {
            "T": self.T,
            "seed": int(self.seed),
            "max_group": int(self.max_group),
            "top_j": int(self.top_j),
            "height_bound": None if height is None else int(height),
            "prune_steps": list(self.prune_steps),
            "backend": self.backend,
            "partitions": self.partitions,
        }

    def merge_forest(self, g, checkpoint_dir=None, resume: bool = False,
                     checkpoint_every: int = 1):
        """Run the T merge iterations only; returns ``(state, pg)`` — the
        merge-forest state and the partitioned graph. Per-stage wall
        seconds, and the seconds of the spans inside them (`SPAN_STATS`),
        land in ``self.stats``, read from this run's spans; the
        partition-sweep benchmark reads the merge phase from there. The
        count of each span is in ``self.stats["span_counts"]``.

        With ``checkpoint_dir`` set, the iteration's applied plan log is
        committed atomically after every ``checkpoint_every``-th iteration
        (`core/checkpoint.PlanCheckpointer`); ``resume=True`` replays the
        newest committed log and continues from the next iteration — the
        resumed summary is bit-identical to an uninterrupted run on every
        backend and partition count (DESIGN.md §11)."""
        from repro.core.transfer import GLOBAL as TRANSFER

        spans0 = SPANS.snapshot()
        counts0 = COUNTS.snapshot()
        with span("setup"):
            pg = as_partitioned(g, self.partitions)
            state = SluggerState(pg.to_graph())
            transfer0 = TRANSFER.snapshot()  # run-context init counts
            deg_mark = faults.DEGRADATIONS.count()  # … and a bank refusal
            self._setup_dispatches(state.g)
        self.stats = {"merges": 0, "transfer_iters": []}
        transfer_prev = transfer0
        spans_prev = SPANS.snapshot()
        counts_prev = COUNTS.snapshot()
        ckpt = None
        fingerprint = None
        plan_log: list = []
        t_start = 1
        if checkpoint_dir is not None:
            from repro.core.checkpoint import PlanCheckpointer, \
                graph_fingerprint
            fingerprint = graph_fingerprint(state.g)
            ckpt = PlanCheckpointer(checkpoint_dir)
            if resume:
                loaded = ckpt.load_latest(fingerprint, self._config())
                if loaded is not None:
                    t_done, plan_log = loaded
                    with span("stage.exchange"):
                        for plans in plan_log:
                            self.stats["merges"] += self._replay_plans(
                                state, plans)
                    t_start = t_done + 1
                    self.stats["resumed_from"] = t_done
                    log.info("resumed from checkpoint at iter %d (%d plans "
                             "replayed)", t_done,
                             sum(len(p) for p in plan_log))
        iter_streams = np.random.SeedSequence(self.seed).spawn(max(self.T, 1))
        for t in range(t_start, self.T + 1):
            theta = 0.0 if t == self.T else 1.0 / (1 + t)
            ctx = IterationContext(t, theta, state, pg)
            ctx.ss_groups, ctx.ss_merge = iter_streams[t - 1].spawn(2)
            for name in STAGE_ORDER:
                with span(f"stage.{name}"):
                    try:
                        self.stages[name](self, ctx)
                    except faults.BankFault as e:
                        # a bank extraction or a device sweep died
                        # mid-stage: degrade, then rebuild pack onward
                        # against the same iteration-start snapshot and
                        # spawned streams (pure functions → identical
                        # decisions, DESIGN.md §11)
                        self._degrade_to_host(
                            ctx.state, "resident.bank.extract"
                            if self._run_ctx is not None
                            else "resident.sweep", e)
                        self.stages["pack"](self, ctx)
                        if name == "merge_round":
                            self.stages["merge_round"](self, ctx)
                faults.check(f"engine.{name}", iteration=t)
            self.stats["merges"] += ctx.merges
            if ckpt is not None:
                plan_log.append(ctx.plans)
                if t % max(1, checkpoint_every) == 0 or t == self.T:
                    with span("checkpoint"):
                        ckpt.save(t, plan_log, fingerprint, self._config())
            snap = TRANSFER.snapshot()
            it_transfer = TRANSFER.delta_since(transfer_prev, now=snap)
            self.stats["transfer_iters"].append(it_transfer)
            transfer_prev = snap
            it_spans = SPANS.delta_since(spans_prev)
            spans_prev = SPANS.snapshot()
            it_counts = COUNTS.delta_since(counts_prev)
            counts_prev = COUNTS.snapshot()
            log.info(
                "iter %3d: θ=%.3f groups=%d merges=%d roots=%d parts=%d "
                "devices=%d host_sweeps=%d device_sweeps=%d "
                "sweep_devices=%d chunks=%d rounds=%d",
                t, theta, len(ctx.groups), ctx.merges, state.alive.size,
                self.partitions, self._devices,
                it_spans.get("merge.host_sweep", {}).get("count", 0),
                it_spans.get("merge.device_sweep", {}).get("count", 0),
                it_counts.get("mesh.sweep_devices", 0),
                it_spans.get("merge.chunk", {}).get("count", 0),
                it_transfer["rounds"])
        self.stats["transfer"] = TRANSFER.delta_since(transfer0)
        self.stats["degradations"] = faults.DEGRADATIONS.count() - deg_mark
        spans = SPANS.delta_since(spans0)
        for key, (name, field) in SPAN_STATS.items():
            self.stats[key] = float(spans.get(name, {}).get(field, 0.0))
        self.stats["span_counts"] = {name: d["count"]
                                     for name, d in spans.items()}
        counts = COUNTS.delta_since(counts0)
        for key in COUNT_STATS:
            self.stats[key] = float(counts.get(key, 0))
        return state, pg

    def run(self, g, checkpoint_dir=None, resume: bool = False,
            checkpoint_every: int = 1):
        """Summarize end to end; returns the (pruned) `Summary`."""
        state, pg = self.merge_forest(g, checkpoint_dir=checkpoint_dir,
                                      resume=resume,
                                      checkpoint_every=checkpoint_every)
        owner = pg.owner if self.partitions > 1 else None
        counts0 = COUNTS.snapshot()
        with span("emit") as sp:
            summary = _emit_encoding(state, backend=self.backend, owner=owner)
        self.stats["emit"] = sp.wall
        if self.prune_steps:
            with span("prune") as sp:
                summary = prune(summary, steps=self.prune_steps,
                                partition_map=owner)
            self.stats["prune"] = sp.wall
        counts = COUNTS.delta_since(counts0)
        for key in POST_COUNT_STATS:
            self.stats[key] = float(counts.get(key, 0))
        log.info("post-merge: emit %.3fs prune %.3fs edges=%d sorts=%d "
                 "sort_fallbacks=%d", self.stats["emit"],
                 self.stats.get("prune", 0.0),
                 summary.edges.shape[0], counts.get("post.sorts", 0),
                 counts.get("post.sort_fallbacks", 0))
        return summary
