"""Queue sweep of a group over 128 members on the device (DESIGN.md §9).

An oversized candidate group is swept in one device program
(`ref.queue_sweep`) from a one-group arena with two sources: on the bank
path it is extracted from the adjacency bank, and on a mesh (no bank) it
is a host-filled chunk uploaded whole to one device. Either way its
ordered merge list must be the one `_sweep_sequential` records on a host
`GroupWorkspace` of the same group and queue permutation, pair for pair.
The groups here are hub leaves of a small graph after two applied merge
batches, so extraction resolves through a composed root map; the leaves
overlap in few neighbours, so rank keys tie and the queue position
decides.
"""
import json
import logging
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro import faults
from repro.core.engine import SummarizerEngine
from repro.core.merging import (_BATCH_MAX_GROUP, GroupWorkspace,
                                build_merge_work)
from repro.core.slugger import SluggerState
from repro.core.spans import GLOBAL as SPANS
from repro.graphs import generators as GG
from repro.graphs.csr import Graph

jax = pytest.importorskip("jax")

CLIQUES, CLIQUE = 30, 6
HUB = CLIQUES * CLIQUE
LEAVES = 540


def _hub_graph() -> Graph:
    """Caveman cliques, one hub over ``LEAVES`` leaves, and two edges from
    each leaf to random clique nodes: rows overlap in the hub and in a few
    clique nodes, so rank keys tie often, and so do Savings of candidates
    whose keys differ (where ranking a short tail would change the pick)."""
    g0 = GG.caveman(CLIQUES, CLIQUE, 0.05, seed=3)
    src = np.repeat(np.arange(g0.n), np.diff(g0.indptr))
    keep = src < g0.indices
    leaves = np.arange(HUB + 1, HUB + 1 + LEAVES)
    rng = np.random.default_rng(0)
    edges = np.concatenate([
        np.stack([src[keep], g0.indices[keep]], axis=1),
        np.stack([np.full(LEAVES, HUB), leaves], axis=1),
        np.stack([leaves, rng.integers(0, HUB, LEAVES)], axis=1),
        np.stack([leaves, rng.integers(0, HUB, LEAVES)], axis=1)])
    return Graph.from_edges(HUB + 1 + LEAVES, edges.astype(np.int64))


def _premerged(bank: bool = True):
    """Host state (and, with ``bank``, the run context whose bank follows
    it) after two applied batches; returns the graph, state, run context
    (or None) and the leaf-derived alive roots, shuffled."""
    from repro.core.resident import ResidentRunContext

    g = _hub_graph()
    st = SluggerState(g)
    ctx = ResidentRunContext(g, bank=True) if bank else None
    assert ctx is None or ctx.bank is not None

    def apply(A, Z):
        A = np.asarray(A, dtype=np.int64)
        Z = np.asarray(Z, dtype=np.int64)
        M = st.merge_batch(A, Z)
        if ctx is not None:
            ctx.advance([(A, Z, M, st.row_len[M].copy())])
        return M

    leaves = np.arange(HUB + 1, HUB + 1 + LEAVES)
    M1 = apply(leaves[:40:2], leaves[1:40:2])
    M2 = apply(np.concatenate([M1[:5], [0, 6]]),
               np.concatenate([M1[5:10], [1, 7]]))
    roots = np.concatenate([M2[:5], M1[10:], leaves[40:]])
    roots = np.random.default_rng(9).permutation(roots)
    return g, st, ctx, roots


@pytest.fixture(scope="module")
def premerged():
    return _premerged()


def _plan_pairs(st, ctx, grp, theta, height_bound, seed, top_j, source):
    """One group through `build_merge_work`, swept by ``source``: the
    numpy backend's host sweep (``"host"``), the bank path's device sweep
    of an extracted shell (``"bank"``), or the mesh path's device sweep of
    a host-filled dense chunk placed on that device (a jax device);
    returns the recorded plan as a list of (a, z) local rows, one merge
    per round."""
    from repro.core.resident import ResidentBitmapArena

    if source == "bank":
        kw = dict(backend="resident", shell_workspaces=True,
                  resident_factory=lambda ws: ResidentBitmapArena.from_bank(
                      ctx.bank, ws, ctx._res_map, top_j=top_j))
    elif source == "host":
        kw = dict(backend="numpy")
    else:
        kw = dict(backend="resident", sweep_devices=[source])
    plans, thunks = build_merge_work(
        st, [grp], theta, group_seeds=np.array([seed], dtype=np.uint64),
        rng_of=lambda i: np.random.default_rng(seed), top_j=top_j,
        height_bound=height_bound, **kw)
    assert len(thunks) == 1
    mark = SPANS.snapshot()
    merges = thunks[0]()
    swept = SPANS.delta_since(mark)
    # the sweep ran where the source says: on the host or on a device
    assert ("merge.device_sweep" in swept) == (source != "host")
    assert ("merge.host_sweep" in swept) == (source == "host")
    rounds = plans[0].rounds
    assert merges == len(rounds)
    assert all(a.size == z.size == 1 for a, z in rounds)
    return [(int(a[0]), int(z[0])) for a, z in rounds]


# the dense source swept on a device other than the default, in a process
# of four virtual CPU devices (they must exist before JAX starts); prints
# the plan's pairs and the devices the swept arena lived on
PLACED_SWEEP = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import json
    import jax
    sys.path.insert(0, sys.argv[1])
    import test_device_sweep as T
    from repro.core.resident import ResidentBitmapArena

    k, theta, height_bound = json.loads(sys.argv[2])
    _, st, _, roots = T._premerged(bank=False)
    seen = []
    sweep = ResidentBitmapArena.queue_sweep

    def spy(arena, *args):
        seen.append(sorted(d.id for d in arena._bits.devices()))
        return sweep(arena, *args)

    ResidentBitmapArena.queue_sweep = spy
    device = jax.devices()[2]
    pairs = T._plan_pairs(st, None, roots[:k], theta, height_bound, 41, 16,
                          device)
    print("RESULT " + json.dumps({"pairs": pairs, "seen": seen,
                                  "default": jax.devices()[0].id,
                                  "device": device.id}))
""")


def _placed_pairs(k, theta, height_bound):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(Path(__file__).resolve().parents[1] / "src"),
         env.get("PYTHONPATH", "")])
    r = subprocess.run(
        [sys.executable, "-c", PLACED_SWEEP,
         str(Path(__file__).resolve().parent),
         json.dumps([k, theta, height_bound])],
        capture_output=True, text=True, env=env, timeout=300)
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    assert r.returncode == 0 and lines, r.stderr[-3000:]
    out = json.loads(lines[-1][len("RESULT "):])
    # the one sweep ran on its own device, not the default one
    assert out["device"] != out["default"]
    assert out["seen"] == [[out["device"]]]
    return [tuple(p) for p in out["pairs"]]


# each case sweeps one group on the device from one arena source: the
# bank's extracted shell, a dense chunk on the default device, or (one
# case, four virtual devices) a dense chunk placed on another device
SWEEP_CASES = [
    *(pytest.param(k, theta, hb, source,
                   id=f"{k}-{theta}-{hb}" + ("" if source == "bank"
                                             else f"-{source}"))
      for source in ("bank", "dense")
      for k in (129, 300, 500) for theta in (0.0, 0.5) for hb in (None, 2)),
    pytest.param(300, 0.0, None, "placed", id="300-0.0-None-placed"),
]


@pytest.mark.parametrize("k, theta, height_bound, source", SWEEP_CASES)
def test_device_sweep_matches_host_sweep(premerged, k, theta, height_bound,
                                         source):
    g, st, ctx, roots = premerged
    grp = roots[:k]
    ws = GroupWorkspace(st, grp)
    keys = ws.rank_to(0, np.arange(1, k))
    assert np.unique(keys).size < k - 1        # tied rank keys are present
    want = _plan_pairs(st, ctx, grp, theta, height_bound, 41, 16, "host")
    if source == "placed":
        got = _placed_pairs(k, theta, height_bound)
    else:
        got = _plan_pairs(st, ctx, grp, theta, height_bound, 41, 16,
                          "bank" if source == "bank" else jax.devices()[0])
    assert got == want
    if theta == 0.0:
        assert len(want) > 10


def test_device_sweep_unranked_queue_order(premerged):
    """With ``top_j`` above the group size no pop ranks its candidates:
    every one is the ≤ top_j tail, taken in queue order."""
    g, st, ctx, roots = premerged
    grp = roots[:129]
    want = _plan_pairs(st, ctx, grp, 0.0, None, 7, 200, "host")
    got = _plan_pairs(st, ctx, grp, 0.0, None, 7, 200, "bank")
    assert got == want and len(want) > 10


# -- engine level -----------------------------------------------------------
def _kronecker():
    return GG.rmat(11, 8, seed=1)


@pytest.fixture(scope="module")
def numpy_job():
    g = _kronecker()
    sizes: list = []

    def stage_group(engine, ctx):
        SummarizerEngine.stage_group(engine, ctx)
        sizes.extend(len(grp) for grp in ctx.groups)

    eng = SummarizerEngine(backend="numpy", T=2, seed=3,
                           stages={"group": stage_group})
    return g, eng.run(g), sum(s > _BATCH_MAX_GROUP for s in sizes)


def _same(a, b):
    assert np.array_equal(np.asarray(a.parent), np.asarray(b.parent))
    assert np.array_equal(np.asarray(a.edges), np.asarray(b.edges))


def test_resident_engine_sweeps_oversized_groups_on_device(numpy_job,
                                                           caplog):
    g, want, n_over = numpy_job
    assert n_over >= 2
    eng = SummarizerEngine(backend="resident", T=2, seed=3, workers=4)
    with caplog.at_level(logging.INFO, logger="repro.engine"):
        got = eng.run(g)
    _same(got, want)
    counts = eng.stats["span_counts"]
    assert counts["merge.device_sweep"] == n_over
    assert counts.get("merge.host_sweep", 0) == 0
    assert eng.stats["merge.host_sweep"] == 0.0
    assert eng.stats["merge.device_sweep"] > 0.0
    assert eng.stats["degradations"] == 0
    lines = [r.getMessage() for r in caplog.records
             if "device_sweeps=" in r.getMessage()]
    assert sum(int(s.split("device_sweeps=")[1].split()[0])
               for s in lines) == n_over


def test_device_sweep_fault_degrades_to_host_sweep(numpy_job):
    g, want, _ = numpy_job
    eng = SummarizerEngine(backend="resident", T=2, seed=3, workers=4)
    with faults.inject("kernel.bitset_fold.sweep"):
        got = eng.run(g)
    assert eng.stats["degradations"] >= 1
    assert eng._run_ctx is None
    assert eng.stats["span_counts"].get("merge.host_sweep", 0) >= 1
    _same(got, want)
