"""The comparison that decides ``correct``, and the reference it compares
with, on the CPU at small sizes."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np
import pytest

from chipbench import correct, reference

HERE = Path(__file__).resolve().parent


def _generate(kind: str, cfg: dict, seed: int):
    path = HERE / "generators" / f"{kind}.py"
    spec = importlib.util.spec_from_file_location(f"gen_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.generate(cfg, seed)


def _kronecker(scale: int) -> dict:
    return {"scale": scale, "edge_factor": 16, "structure_seed": 0,
            "initiator": [0.57, 0.19, 0.19, 0.05], "permute": True}


@pytest.fixture(scope="module", params=[9, 10])
def summarized(request):
    n, edges = _generate("kronecker", _kronecker(request.param), 2**31 + 11)
    return reference.summarize(n, edges, T=2, seed=2**31 + 11)


def _one(out):
    return [(out["parent"], out["edges"], 0)]


def test_reference_output_is_lossless_and_passes(summarized):
    found = correct.total(correct.compare(_one(summarized), summarized))
    assert found == {name: 0 for name in correct.LIMITS}
    assert correct.verdict(found)


def test_one_merge_decision_undone_is_lossless_but_rejected(summarized):
    """Undo one merge of two leaves and re-encode: the summary still
    decodes to the input graph, and the comparison still refuses it."""
    g, forest = summarized["graph"], summarized["forest"].copy()
    kids = np.flatnonzero((forest[: g.n] >= 0))
    counts = np.bincount(forest[kids], minlength=forest.size)
    # a parent both of whose children are leaves and that is itself a root
    cand = [p for p in np.flatnonzero(counts == 2)
            if forest[p] == -1]
    assert cand, "the small graph merged no pair of leaves into a root"
    p = cand[0]
    forest[forest == p] = -1
    # drop p by relabelling every later id down by one
    keep = np.ones(forest.size, dtype=bool)
    keep[p] = False
    remap = np.cumsum(keep) - 1
    forest = np.where(forest >= 0, remap[np.maximum(forest, 0)], forest)[keep]
    parent, edges = reference.encode_and_prune(g, forest)
    assert correct.lossless_mismatch(g, parent, edges) == 0
    found = correct.total(correct.compare([(parent, edges, 0)], summarized))
    assert found["lossless_mismatch"] == 0
    assert found["parent_mismatch"] + found["edge_mismatch"] > 0
    assert not correct.verdict(found)


def test_a_flipped_edge_sign_is_caught_by_both_checks(summarized):
    edges = summarized["edges"].copy()
    edges[0, 2] = -edges[0, 2]
    found = correct.total(correct.compare(
        [(summarized["parent"], edges, 0)], summarized))
    assert found["edge_mismatch"] == 2
    assert found["lossless_mismatch"] > 0
    assert not correct.verdict(found)


def test_a_degradation_alone_fails_the_run(summarized):
    found = correct.total(correct.compare(
        [(summarized["parent"], summarized["edges"], 1)], summarized))
    assert found["degradations"] == 1 and not correct.verdict(found)


def test_counts_sum_over_every_job(summarized):
    bad = summarized["edges"][1:]
    jobs = _one(summarized) + [(summarized["parent"], bad, 0)] * 2
    per_job = correct.compare(jobs, summarized)
    assert [correct.verdict(f) for f in per_job] == [True, False, False]
    assert correct.total(per_job)["edge_mismatch"] == 2


@pytest.mark.parametrize("scale,T,seed", [(9, 3, 77), (10, 2, 2**31 + 3),
                                          (11, 2, 5)])
def test_reference_equals_the_program_numpy_engine(scale, T, seed):
    """The reference, written apart from the program, makes the program's
    decisions, bit for bit."""
    from repro.core.engine import SummarizerEngine
    from repro.graphs.csr import Graph

    n, edges = _generate("kronecker", _kronecker(scale), seed)
    ref = reference.summarize(n, edges, T=T, seed=seed)
    s = SummarizerEngine(backend="numpy", T=T, seed=seed).run(
        Graph.from_edges(n, edges))
    assert np.array_equal(s.parent, ref["parent"])
    assert np.array_equal(s.edges, ref["edges"])


def test_decode_matches_a_hand_built_summary():
    # leaves 0..3; supernode 4 = {0, 1}; a p-edge 4-2 and an n-edge 1-2
    # give exactly the edge 0-2, and a p-edge 2-3 gives 2-3
    parent = np.array([4, 4, -1, -1, -1])
    edges = np.array([[2, 4, 1], [1, 2, -1], [2, 3, 1]])
    assert correct.decode(4, parent, edges).tolist() == [0 * 4 + 2, 2 * 4 + 3]
