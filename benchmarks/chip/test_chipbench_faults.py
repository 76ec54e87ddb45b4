"""A whole run of the summarize cell on the CPU at a tiny size, past the
harness's look for a chip, once sound and once with each fault the cell can
have planted in the timed path underneath: ``correct`` must come out true
and then false. (The cell runs on one chip, so it has no exchange between
chips to leave out; the engine's own exchange stage is left out instead.)"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import bench

HERE = Path(__file__).resolve().parent
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_root")
    (root / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "generator": "kronecker", "structure_seed": 0,
         "scale": 8,
         "edge_factor": 16, "initiator": [0.57, 0.19, 0.19, 0.05],
         "permute": True, "T": 2}))
    spec = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test", "file": "tiny.json",
                        "reduced": [], "why": "test"}]
    spec["workloads"] = [{"name": "tiny.summarize", "config": "tiny",
                          "traffic": "summarize", "chips": 1, "why": "test"}]
    for m in spec["per_layer"]:
        m["workloads"] = ["tiny.summarize"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _run(root, trace=False):
    cell = bench.resolve(root, "tiny.summarize", 2**31 + 3, 0.0, trace,
                         time.perf_counter(), log=lambda msg: None)
    return bench.run_cell(cell, DEVICE)


@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(tiny_root, trace):
    out = _run(tiny_root, trace)
    assert out["correct"] is True and out["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    names = set(out["metrics"])
    if trace:
        assert {"stage_s.merge_round", "round_trips.summarize"} <= names
    else:
        assert names == {"summarize_edges_per_s", "setup_s"}
        assert out["metrics"]["summarize_edges_per_s"]["value"] > 0


def _no_merge_round(engine, ctx, stage):
    """A step that returns its state unchanged."""


def _half_the_chunks(engine, ctx, stage):
    """Half of the batch left out: only the first half of the round's
    chunks is swept."""
    for thunk in ctx.thunks[: len(ctx.thunks) // 2]:
        thunk()


def _no_exchange(engine, ctx, stage):
    """The exchange left out: no recorded plan reaches the state."""
    ctx.merges = 0


def _one_decision_dropped(engine, ctx, stage):
    """One merge decision altered where it is made: the last recorded
    round of the first group that merged loses a pair."""
    stage(engine, ctx)
    for plan in ctx.plans:
        if plan is not None and plan.rounds:
            a, z = plan.rounds[-1]
            plan.rounds[-1] = (a[1:], z[1:])
            break


FAULTS = [("state_unchanged", "merge_round", _no_merge_round),
          ("half_the_batch", "merge_round", _half_the_chunks),
          ("exchange_left_out", "exchange", _no_exchange),
          ("one_decision_dropped", "merge_round", _one_decision_dropped)]


@pytest.mark.parametrize("stage,fault", [f[1:] for f in FAULTS],
                         ids=[f[0] for f in FAULTS])
def test_broken_stage_is_not_correct(tiny_root, monkeypatch, stage, fault):
    from repro.core.engine import SummarizerEngine

    sound = getattr(SummarizerEngine, f"stage_{stage}")
    monkeypatch.setattr(SummarizerEngine, f"stage_{stage}",
                        lambda engine, ctx: fault(engine, ctx, sound))
    out = _run(tiny_root)
    # a window of one pass runs one job per job seed; the jobs of one job
    # seed are compared, and each of them fails
    pool = json.loads((HERE / "traffic" / "summarize.json").read_text())
    passes = out["attempted"] // len(pool["job_seeds"])
    assert out["correct"] is False and out["failed"] == passes >= 1


def test_altered_answer_is_not_correct(tiny_root, monkeypatch):
    """An answer altered where it is produced: one summary edge's sign
    flipped in what the engine returns."""
    from repro.core.engine import SummarizerEngine

    run = SummarizerEngine.run

    def flipped(self, g, *a, **k):
        s = run(self, g, *a, **k)
        s.edges = np.asarray(s.edges).copy()
        s.edges[0, 2] = -s.edges[0, 2]
        return s

    monkeypatch.setattr(SummarizerEngine, "run", flipped)
    out = _run(tiny_root)
    assert out["correct"] is False
    assert out["checks"]["lossless_mismatch"]["value"] > 0
