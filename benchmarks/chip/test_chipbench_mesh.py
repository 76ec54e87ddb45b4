"""The four-chip summarize cell's driver (`drivers/summarize_mesh.py`) on
the CPU at a tiny size, on four virtual devices: a sound run is ``correct``
and its jobs equal the one-device driver's bit for bit, a planted change of
one merge decision reads ``correct`` false, the check the driver makes of
every job refuses each job of the one-device path, and a run of a program
that cannot show the path it took is refused with no result.

Four devices need ``XLA_FLAGS`` set before JAX starts, so the runs happen in
subprocesses, each of which prints what the tests check as one JSON line."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import hashlib, json, time, tempfile
    from pathlib import Path
    import numpy as np

    here, root, mode = Path(sys.argv[1]), Path(sys.argv[2]), sys.argv[3]
    sys.path[:0] = [str(here), str(root / "src")]
    from chipbench import bench
    from repro.core import engine as E

    tiny = Path(tempfile.mkdtemp())
    (tiny / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "generator": "kronecker", "structure_seed": 0,
         "scale": 8, "edge_factor": 16, "initiator": [0.57, 0.19, 0.19, 0.05],
         "permute": True, "T": 2}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test", "file": "tiny.json",
                        "reduced": [], "why": "test"}]
    spec["workloads"] = [
        {"name": "tiny.summarize", "config": "tiny", "traffic": "summarize",
         "chips": 1, "why": "test"},
        {"name": "tiny.mesh4", "config": "tiny",
         "traffic": "summarize-mesh4", "chips": 4, "why": "test"}]
    for m in spec["per_layer"]:
        m["workloads"] = ["tiny.summarize", "tiny.mesh4"]
    (tiny / "BENCHMARK.json").write_text(json.dumps(spec))

    # a digest of every job's summary, by job seed, and the mesh driver's
    # verdict on the job's tallies
    summaries, verdicts = {}, []
    engine_run = E.SummarizerEngine.run
    mesh_driver = bench.load_module(here / "drivers" / "summarize_mesh.py")

    def recorded(self, g, *a, **k):
        s = engine_run(self, g, *a, **k)
        digest = hashlib.sha256(np.asarray(s.parent).tobytes()
                                + np.asarray(s.edges).tobytes())
        summaries.setdefault(str(self.seed), []).append(digest.hexdigest())
        job = {"job_seed": self.seed, "stages": {
            k: v for k, v in self.stats.items() if isinstance(v, float)}}
        try:
            mesh_driver.check_mesh_job(job, 4)
            verdicts.append(None)
        except RuntimeError as e:
            verdicts.append(str(e))
        return s

    E.SummarizerEngine.run = recorded

    def run(workload, trace=False):
        cell = bench.resolve(tiny, workload, 2**31 + 7, 0.0, trace,
                             time.perf_counter(), log=lambda msg: None)
        device = {"platform": "cpu", "kind": "cpu",
                  "count": cell.workload["chips"]}
        return bench.run_cell(cell, device)

    def refused(workload):
        try:
            run(workload)
        except RuntimeError as e:
            return str(e)
        return None

    out = {}
    if mode == "one":
        out["one"] = run("tiny.summarize")
    else:
        out["mesh"] = run("tiny.mesh4", trace=True)
        out["mesh_untraced"] = run("tiny.mesh4")
    out["summaries"] = {k: list(v) for k, v in summaries.items()}
    out["verdicts"] = list(verdicts)
    if mode == "mesh":
        # one merge decision dropped where it is made: the last recorded
        # round of the first group that merged loses a pair
        sound = E.SummarizerEngine.stage_merge_round

        def one_dropped(engine, ctx):
            sound(engine, ctx)
            for plan in ctx.plans:
                if plan is not None and plan.rounds:
                    a, z = plan.rounds[-1]
                    plan.rounds[-1] = (a[1:], z[1:])
                    break

        E.SummarizerEngine.stage_merge_round = one_dropped
        out["planted"] = run("tiny.mesh4")
        E.SummarizerEngine.stage_merge_round = sound

        # a program with no tally of sharded arena rows
        E.COUNT_STATS = ()
        out["no_tally"] = refused("tiny.mesh4")
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def runs():
    """The four-device driver's runs, and in a process of its own beside
    them the one-device driver's run of the same tiny cell."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    procs = {mode: subprocess.Popen(
        [sys.executable, "-c", SCRIPT, str(HERE), str(ROOT), mode],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        cwd=ROOT) for mode in ("mesh", "one")}
    out = {}
    for mode, proc in procs.items():
        stdout, stderr = proc.communicate(timeout=300)
        lines = [ln for ln in stdout.splitlines() if ln.startswith("RESULT ")]
        assert proc.returncode == 0 and lines, stderr[-3000:]
        out[mode] = json.loads(lines[-1][len("RESULT "):])
    return {**out["mesh"], "one": out["one"]["one"],
            "one_summaries": out["one"]["summaries"],
            "one_verdicts": out["one"]["verdicts"]}


def test_sound_mesh_run_is_correct_and_equals_one_device(runs):
    for key in ("mesh", "mesh_untraced", "one"):
        out = runs[key]
        assert out["correct"] is True and out["failed"] == 0, key
        assert all(c["value"] <= c["limit"]
                   for c in out["checks"].values()), key
    assert runs["mesh"]["device"]["count"] == 4
    assert set(runs["mesh_untraced"]["metrics"]) == {
        "summarize_edges_per_s", "setup_s"}
    # every job seed of the pool ran on both paths, every job bit for bit
    # alike: two set-up jobs and a window pass a run, three mesh runs
    mesh, one = runs["summaries"], runs["one_summaries"]
    assert set(mesh) == set(one) and len(one) == 2
    for seed, digests in one.items():
        assert len(digests) == 2 and len(mesh[seed]) == 4
        assert set(digests) | set(mesh[seed]) == {digests[0]}, seed


def test_traced_mesh_run_reports_the_mesh_metrics(runs):
    metrics = runs["mesh"]["metrics"]
    for name in ("mesh_s.shingle", "mesh_s.upload", "mesh_s.pack_fill"):
        assert metrics[name]["value"] > 0.0, name
    assert 0.0 <= metrics["mesh.pad_share"]["value"] < 100.0
    assert metrics["merge_s.host_sweep"]["value"] >= 0.0


def test_planted_decision_change_is_not_correct(runs):
    out = runs["planted"]
    assert out["correct"] is False and out["failed"] >= 1
    assert out["checks"]["parent_mismatch"]["value"] \
        + out["checks"]["edge_mismatch"]["value"] > 0


def test_one_device_path_is_refused(runs):
    """The mesh driver's check of every job passes each mesh job and
    refuses each job of the one-device path; a program that keeps no tally
    of sharded arena rows is refused before its first job."""
    assert runs["verdicts"][:8] == [None] * 8
    assert len(runs["one_verdicts"]) == 4
    assert all("one-device path" in (v or "") for v in runs["one_verdicts"])
    assert "counts no mesh-sharded arena rows" in (runs["no_tally"] or "")


def test_mesh_cell_runs_the_one_chip_cells_graph_on_its_own_deployment():
    """The four-chip cell names a configuration of its own, a four-chip
    v5e deployment with its own source, whose graph, cut and job are the
    one-chip cell's key for key, so both cells do the same work."""
    sys.path.insert(0, str(HERE))
    from chipbench import bench

    cells = {name: bench.resolve(ROOT, name, 2**31 + 7, 10.0, False, 0.0)
             for name in ("graph500.summarize", "graph500.summarize-mesh4")}
    one, mesh = cells["graph500.summarize"], cells["graph500.summarize-mesh4"]
    entry = {c["name"]: c for c in one.bench["configs"]}
    assert mesh.workload["config"] == "graph500-mesh4" != one.workload["config"]
    assert entry["graph500-mesh4"]["source"] != entry["graph500"]["source"]
    assert mesh.config["graph_source"] == one.config["source"]
    own = {"name", "source", "graph_source", "deployment"}
    assert set(mesh.config) - own == set(one.config) - {"name", "source",
                                                        "deployment"}
    for key in set(one.config) - own:
        assert mesh.config[key] == one.config[key], key
    assert sorted(entry["graph500-mesh4"]["reduced"]) == sorted(
        mesh.config["reduced"])
    assert mesh.traffic["job_seeds"] == one.traffic["job_seeds"]
    assert mesh.traffic["devices"] == mesh.workload["chips"] == 4
    n1, e1 = one.generator().generate(dict(one.config, scale=6), 5)
    n4, e4 = mesh.generator().generate(dict(mesh.config, scale=6), 5)
    assert n1 == n4 and (e1 == e4).all()
