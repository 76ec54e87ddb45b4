"""Spans inside the summarizer (`core/spans.py`): the totals under threads,
self time of nested spans, snapshot and delta, what one resident job
reports in ``engine.stats``, and the spans landing in a profiler trace
without changing the summary."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core.engine import (COUNT_STATS, SPAN_STATS, STAGE_ORDER,
                               SummarizerEngine)
from repro.core.merging import _BATCH_MAX_GROUP
from repro.core.spans import Counts, SpanTotals, span
from repro.graphs import generators as GG
from repro.graphs.csr import Graph

HUB_LEAVES = 200


def _busy(seconds: float) -> None:
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        pass


def test_totals_lose_no_count_under_threads():
    totals = SpanTotals()
    n_threads, per_thread = 8, 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per_thread):
                with span("outer", totals):
                    with span("inner", totals):
                        pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    snap = totals.snapshot()
    assert snap["outer"]["count"] == snap["inner"]["count"] \
        == n_threads * per_thread
    # each thread's inner spans are its outer spans' children, never
    # another thread's
    assert snap["outer"]["self"] == pytest.approx(
        snap["outer"]["wall"] - snap["inner"]["wall"], rel=1e-9, abs=1e-9)


def test_self_time_of_nested_spans():
    totals = SpanTotals()
    with span("parent", totals) as parent:
        _busy(0.02)
        with span("child", totals) as c1:
            _busy(0.03)
        with span("child", totals) as c2:
            _busy(0.01)
    snap = totals.snapshot()
    assert snap["parent"]["wall"] == parent.wall
    assert snap["child"]["wall"] == pytest.approx(c1.wall + c2.wall)
    assert snap["parent"]["self"] == pytest.approx(
        parent.wall - c1.wall - c2.wall)
    assert snap["parent"]["self"] >= 0.02
    assert snap["child"]["self"] == pytest.approx(snap["child"]["wall"])
    assert snap["child"]["max"] == max(c1.wall, c2.wall)
    # one thread's CPU seconds, never more than its wall seconds
    assert 0.0 < snap["parent"]["cpu"] <= snap["parent"]["wall"] + 0.01


def test_snapshot_and_delta_since():
    totals = SpanTotals()
    for seconds in (0.004, 0.012, 0.008):
        totals.add("a", seconds, seconds / 2, seconds)
    snap = totals.snapshot()
    assert snap["a"]["count"] == 3
    assert snap["a"]["max"] == 0.012
    assert totals.delta_since(snap) == {}
    # the longest span after the snapshot is shorter than the one before
    for seconds in (0.002, 0.006, 0.001):
        totals.add("a", seconds, 0.0, seconds)
    totals.add("b", 0.5, 0.25, 0.5)
    d = totals.delta_since(snap)
    assert d["a"]["count"] == 3
    assert d["a"]["wall"] == pytest.approx(0.009)
    assert d["a"]["cpu"] == pytest.approx(0.0)
    assert d["a"]["max"] == 0.006
    assert d["b"] == {"count": 1, "wall": 0.5, "cpu": 0.25, "self": 0.5,
                      "max": 0.5}
    assert totals.snapshot()["a"]["max"] == 0.012
    assert totals.delta_since({})["a"]["count"] == 6


def test_counts_snapshot_and_delta_under_threads():
    counts = Counts()
    counts.add("rows", 5)
    snap = counts.snapshot()
    assert snap == {"rows": 5}
    assert counts.delta_since(snap) == {}

    def work():
        for _ in range(1000):
            counts.add("rows", 2)
            counts.add("arenas", 1)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert counts.delta_since(snap) == {"rows": 8000, "arenas": 4000}
    assert counts.delta_since({}) == {"rows": 8005, "arenas": 4000}


MESH_SPANS = ("mesh.shingle", "mesh.upload", "pack.fill")


def _hub_graph(hubs: int = 1) -> Graph:
    """Caveman cliques plus ``hubs`` hubs, each with its own degree-one
    leaves: one hub's leaves share a shingle, so each hub makes one
    candidate group over 128 members, swept on a device; the cliques fill
    batched chunks."""
    g0 = GG.caveman(30, 6, 0.05, seed=3)
    src = np.repeat(np.arange(g0.n), np.diff(g0.indptr))
    keep = src < g0.indices
    parts = [np.stack([src[keep], g0.indices[keep]], axis=1)]
    n = g0.n
    for _ in range(hubs):
        leaves = np.arange(n + 1, n + 1 + HUB_LEAVES)
        parts.append(np.stack([np.full(HUB_LEAVES, n), leaves], axis=1))
        n += 1 + HUB_LEAVES
    return Graph.from_edges(n, np.concatenate(parts).astype(np.int64))


def _job(g, stages=None):
    eng = SummarizerEngine(backend="resident", T=3, seed=2, workers=4,
                           stages=stages)
    return eng, eng.run(g)


@pytest.fixture(scope="module")
def hub_job():
    g = _hub_graph()
    sizes: list = []

    def stage_group(engine, ctx):
        SummarizerEngine.stage_group(engine, ctx)
        sizes.extend(len(grp) for grp in ctx.groups)

    eng, summary = _job(g, {"group": stage_group})
    return g, eng, summary, sizes


def test_job_stats_hold_every_span_key(hub_job):
    g, eng, summary, sizes = hub_job
    st = eng.stats
    for key in SPAN_STATS:
        assert isinstance(st[key], float), key
    for key in ("setup", "merge.device_sweep", "merge.extract", "merge.round",
                "merge.fold", "merge.chunk.self", "merge.thunk.max",
                "merge.thunk.cpu", "exchange.replay",
                "exchange.bank_advance", "emit", "prune"):
        assert st[key] > 0.0, key
    counts = st["span_counts"]
    assert all(isinstance(v, int) for v in counts.values())
    assert counts["setup"] == 1
    # a device sweep is one round trip of its own
    assert (counts["merge.round"] + counts["merge.device_sweep"]
            == st["transfer"]["rounds"])
    assert counts["merge.round"] > 0
    assert counts["merge.device_sweep"] == sum(
        s > _BATCH_MAX_GROUP for s in sizes) >= 1
    assert "merge.host_sweep" not in counts
    assert st["merge.host_sweep"] == 0.0
    assert counts["merge.thunk"] == (counts["merge.device_sweep"]
                                     + counts["merge.chunk"])
    assert counts["merge.extract"] == counts["merge.thunk"]
    assert summary.validate_lossless(g)


def test_one_device_bank_job_reads_no_mesh_span_or_tally(hub_job):
    _, eng, _, _ = hub_job
    st = eng.stats
    for key in MESH_SPANS + COUNT_STATS:
        assert st[key] == 0.0, key
        assert key not in st["span_counts"], key


def test_job_merge_parts_add_up_to_thunk_time(hub_job):
    _, eng, _, _ = hub_job
    st = eng.stats
    parts = (st["merge.device_sweep"] + st["merge.extract"]
             + st["merge.round"] + st["merge.fold"] + st["merge.chunk.self"])
    assert parts == pytest.approx(st["merge.thunk"], rel=0.02)
    assert st["merge.thunk.max"] <= st["merge.thunk"]
    assert st["merge.thunk.max"] <= st["merge_round"]


def test_stage_spans_sum_to_stage_seconds():
    from repro.core.spans import GLOBAL

    g = GG.caveman(12, 6, 0.05, seed=5)
    snap = GLOBAL.snapshot()
    eng, _ = _job(g)
    d = GLOBAL.delta_since(snap)
    assert sum(d[f"stage.{name}"]["wall"] for name in STAGE_ORDER) \
        == pytest.approx(sum(eng.stats[name] for name in STAGE_ORDER),
                         rel=1e-12)
    for name in STAGE_ORDER:
        assert d[f"stage.{name}"]["count"] == eng.T
    assert d["emit"]["wall"] == eng.stats["emit"]
    assert d["prune"]["wall"] == eng.stats["prune"]


def _host_span_names(logdir) -> set:
    """Names of the ``slugger.`` annotations on the host planes of the
    newest profiler trace under ``logdir``."""
    from jax.profiler import ProfileData

    path = max(Path(logdir).rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime)
    pd = ProfileData.from_file(str(path))
    return {ev.name for plane in pd.planes if plane.name.startswith("/host:")
            for ln in plane.lines for ev in ln.events
            if ev.name.startswith("slugger.")}


def test_summary_identical_under_profiler_and_spans_in_trace(hub_job,
                                                             tmp_path):
    import jax

    g, _, plain, _ = hub_job
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, traced = _job(g)
    finally:
        jax.profiler.stop_trace()
    assert np.array_equal(np.asarray(traced.parent), np.asarray(plain.parent))
    assert np.array_equal(np.asarray(traced.edges), np.asarray(plain.edges))
    names = _host_span_names(tmp_path)
    assert "slugger.stage.merge_round" in names
    assert {"slugger.setup", "slugger.merge.thunk", "slugger.merge.chunk",
            "slugger.merge.round", "slugger.merge.device_sweep"} <= names


MESH_JOB = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import json, tempfile
    import numpy as np
    import jax
    from jax.sharding import Mesh
    sys.path.insert(0, sys.argv[1])
    import test_spans as T
    from repro import faults
    from repro.core.engine import SummarizerEngine
    from repro.core.merging import _BATCH_MAX_GROUP

    g = T._hub_graph(hubs=2)
    mesh = Mesh(np.array(jax.devices()), ("data",))
    oversized = []

    def stage_group(engine, ctx):
        SummarizerEngine.stage_group(engine, ctx)
        oversized.append(sum(len(grp) > _BATCH_MAX_GROUP
                             for grp in ctx.groups))

    def job():
        oversized.clear()
        eng = SummarizerEngine(backend="resident", partitions=4, T=3, seed=2,
                               workers=4, mesh=mesh,
                               stages={"group": stage_group})
        s = eng.run(g)
        return eng, s, np.asarray(s.parent), np.asarray(s.edges)

    eng, summary, parent, edges = job()
    n_over = sum(oversized)
    logdir = tempfile.mkdtemp()
    jax.profiler.start_trace(logdir)
    try:
        _, _, t_parent, t_edges = job()
    finally:
        jax.profiler.stop_trace()
    one = SummarizerEngine(backend="resident", partitions=4, T=3, seed=2,
                           workers=4, mesh=Mesh(np.array(jax.devices()[:1]),
                                                ("data",))).run(g)
    with faults.inject("kernel.bitset_fold.sweep"):
        f_eng, f_summary, f_parent, f_edges = job()
    st = {k: v for k, v in eng.stats.items()
          if isinstance(v, (int, float))}
    print("RESULT " + json.dumps({
        "stats": st, "span_counts": eng.stats["span_counts"],
        "oversized": n_over,
        "fault": {"degradations": f_eng.stats["degradations"],
                  "host_sweeps": f_eng.stats["span_counts"].get(
                      "merge.host_sweep", 0),
                  "same": bool(np.array_equal(parent, f_parent)
                               and np.array_equal(edges, f_edges)),
                  "lossless": bool(f_summary.validate_lossless(g))},
        "traced_same": bool(np.array_equal(parent, t_parent)
                            and np.array_equal(edges, t_edges)),
        "one_device_same": bool(np.array_equal(parent, one.parent)
                                and np.array_equal(edges, one.edges)),
        "lossless": bool(summary.validate_lossless(g)),
        "trace_spans": sorted(T._host_span_names(logdir))}))
""")


@pytest.fixture(scope="module")
def mesh_job():
    """One resident job of the hub graph on a mesh of four virtual devices
    (a subprocess: the devices must exist before JAX starts), its summary
    once more under a profiler session, and the one-device summary."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(Path(__file__).resolve().parents[1] / "src"),
         env.get("PYTHONPATH", "")])
    r = subprocess.run(
        [sys.executable, "-c", MESH_JOB, str(Path(__file__).resolve().parent)],
        capture_output=True, text=True, env=env, timeout=300)
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    assert r.returncode == 0 and lines, r.stderr[-3000:]
    return json.loads(lines[-1][len("RESULT "):])


def test_mesh_job_reads_its_spans_and_tallies(mesh_job):
    st, counts = mesh_job["stats"], mesh_job["span_counts"]
    for key in MESH_SPANS:
        assert st[key] > 0.0 and counts[key] > 0, key
    arenas, rows, padded = (st["mesh.arenas"], st["mesh.rows"],
                            st["mesh.rows_padded"])
    assert arenas == counts["merge.chunk"] > 0
    assert 0 < rows <= padded and padded % 4 == 0
    # every arena's bits hold a shard on each of the four devices
    assert st["mesh.shard_devices"] == 4 * arenas
    # no bank on a mesh: each oversized group is swept on one device of
    # the mesh from a host-filled arena, and the sweeps use more than one
    assert "merge.host_sweep" not in counts
    assert counts["merge.device_sweep"] == mesh_job["oversized"] >= 2
    assert st["mesh.sweep_devices"] > 1
    assert counts["mesh.upload"] == 2 * arenas


def test_mesh_device_sweep_fault_degrades_to_host_sweep(mesh_job):
    fault = mesh_job["fault"]
    assert fault["degradations"] >= 1
    assert fault["host_sweeps"] >= 1
    assert fault["same"] and fault["lossless"]


def test_mesh_summary_identical_under_profiler_and_to_one_device(mesh_job):
    assert mesh_job["traced_same"] and mesh_job["one_device_same"]
    assert mesh_job["lossless"]
    assert {"slugger.mesh.shingle", "slugger.mesh.upload",
            "slugger.pack.fill"} <= set(mesh_job["trace_spans"])
