"""Post-merge pipeline equivalence (ISSUE 2 acceptance): the batched emitter
and the IR pruner must match their kept references bit for bit on the same
merge forest, for every merge backend; `Summary.neighbors` must agree with
full decompression row by row."""
import numpy as np
import pytest

from repro.core import summarize
from repro.core.encode_batched import encode_forest
from repro.core.merging import process_group, process_groups
from repro.core.minhash import candidate_groups
from repro.core.pruning import prune
from repro.core.slugger import (SluggerState, _emit_encoding,
                                _emit_encoding_reference)
from repro.core.summary_ir import SummaryIR
from repro.graphs import generators as GG
from repro.graphs.csr import Graph

BACKENDS = ("loop", "numpy")


def _forest(g, backend, T=6, seed=3):
    state = SluggerState(g)
    rng = np.random.default_rng(seed)
    for t in range(1, T + 1):
        theta = 0.0 if t == T else 1.0 / (1 + t)
        groups = candidate_groups(g, state.root_of, state.alive,
                                  seed=seed * 7919 + t, max_group=500)
        if backend == "loop":
            for grp in groups:
                process_group(state, grp, theta, rng)
        else:
            process_groups(state, groups, theta, rng, backend=backend)
    return state


def _graphs():
    return [
        ("er", GG.erdos_renyi(150, 0.04, seed=11)),
        ("caveman", GG.caveman(14, 6, 0.05, seed=13)),
        ("nested", GG.bipartite_nested(32, 31, 5)),
        ("star", GG.star_of_cliques(20, 6, seed=10)),
    ]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name,g", _graphs(), ids=lambda v: v if isinstance(v, str) else "")
def test_batched_emitter_matches_recursive_dp(name, g, backend):
    """Same forest -> bit-identical canonical edge arrays and cost."""
    state = _forest(g, backend)
    ref = _emit_encoding_reference(state)
    new = _emit_encoding(state, backend="numpy")
    assert np.array_equal(ref.edges, new.edges)
    assert ref.cost() == new.cost()
    assert new.decompress() == g


@pytest.mark.parametrize("name,g", _graphs(), ids=lambda v: v if isinstance(v, str) else "")
def test_ir_prune_matches_dict_reference(name, g):
    s = _emit_encoding(_forest(g, "numpy"))
    for steps in [(1,), (1, 2), (1, 2, 3)]:
        a = prune(s, steps=steps, impl="ir")
        b = prune(s, steps=steps, impl="dict")
        assert np.array_equal(a.parent, b.parent), steps
        assert np.array_equal(a.edges, b.edges), steps
        assert a.cost() == b.cost()
        assert a.decompress() == g


def test_equivalence_on_random_graphs():
    rng = np.random.default_rng(7)
    for trial in range(10):
        n = int(rng.integers(2, 36))
        e = rng.integers(0, n, size=(max(int(n * n * rng.random() * 0.5), 1), 2))
        g = Graph.from_edges(n, e)
        state = _forest(g, "numpy", T=4, seed=trial)
        ref = _emit_encoding_reference(state)
        new = _emit_encoding(state)
        assert np.array_equal(ref.edges, new.edges), trial
        a = prune(new, impl="ir")
        b = prune(new, impl="dict")
        assert np.array_equal(a.edges, b.edges), trial
        assert np.array_equal(a.parent, b.parent), trial
        assert a.decompress() == g


@pytest.mark.parametrize("partitions", (1, 4))
def test_kronecker_emission_and_pruning_match_references(partitions):
    """A scale-12 Kronecker graph's forest, emitted (owner buckets when
    partitioned) and pruned (step-3 buckets) by the numpy path: bit-identical
    to the recursive emitter and to the dict pruner."""
    from repro.core.engine import SummarizerEngine

    g = GG.rmat(12, edge_factor=16, seed=12)
    state, pg = SummarizerEngine(backend="numpy", partitions=partitions,
                                 T=2, seed=3).merge_forest(g)
    owner = pg.owner if partitions > 1 else None
    ref = _emit_encoding_reference(state)
    new = _emit_encoding(state, backend="numpy", owner=owner)
    assert np.array_equal(ref.parent, new.parent)
    assert np.array_equal(ref.edges, new.edges)
    a = prune(new, partition_map=owner)
    b = prune(new, impl="dict")
    assert np.array_equal(a.parent, b.parent)
    assert np.array_equal(a.edges, b.edges)
    assert a.validate_lossless(g)


def test_nonbinary_forest_rejected_by_batched_emitter():
    # 3 leaves under one parent: encode_forest must refuse (the emission
    # wrapper then falls back to the recursive reference)
    parent = np.array([3, 3, 3, -1], dtype=np.int64)
    ir = SummaryIR(parent, 3)
    with pytest.raises(ValueError):
        encode_forest(ir, np.array([0]), np.array([1]))


def test_prune_step3_on_edgeless_summary():
    """Regression: step 3 alone must still splice edge-free supernodes (its
    benefit test accepts them), identically in both implementations."""
    from repro.core.summary import Summary
    s = Summary(n_leaves=2, parent=np.array([2, 2, -1], dtype=np.int64),
                edges=np.zeros((0, 3), dtype=np.int64))
    a = prune(s, steps=(3,), impl="ir")
    b = prune(s, steps=(3,), impl="dict")
    assert np.array_equal(a.parent, b.parent)
    assert np.array_equal(a.parent, np.array([-1, -1, -2]))
    assert a.edges.shape == b.edges.shape == (0, 3)


def test_prune_deterministic_identical_arrays():
    """Satellite: two prune runs on the same summary produce identical edge
    arrays (stable candidate ordering + canonical export, no dict/set
    iteration dependence)."""
    for name, g in _graphs():
        s = _emit_encoding(_forest(g, "numpy"))
        for impl in ("ir", "dict"):
            a = prune(s, impl=impl)
            b = prune(s, impl=impl)
            assert np.array_equal(a.edges, b.edges), (name, impl)
            assert np.array_equal(a.parent, b.parent), (name, impl)


def test_neighbors_equals_decompress_rows():
    """Satellite (Algorithm 4 property): neighbors(v) == v-th row of the
    decompressed graph for every v, before and after pruning."""
    rng = np.random.default_rng(3)
    for trial in range(8):
        n = int(rng.integers(2, 30))
        e = rng.integers(0, n, size=(max(int(n * n * rng.random() * 0.6), 1), 2))
        g = Graph.from_edges(n, e)
        for steps in [(), (1, 2, 3)]:
            s = summarize(g, T=4, seed=trial, prune_steps=steps)
            dec = s.decompress()
            for v in range(n):
                assert np.array_equal(s.neighbors(v), dec.neighbors(v).astype(np.int64)), (
                    trial, steps, v)


def test_neighbors_property_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(n=st.integers(min_value=1, max_value=24),
           density=st.floats(min_value=0.0, max_value=0.7),
           seed=st.integers(min_value=0, max_value=2 ** 31 - 1))
    def inner(n, density, seed):
        rng = np.random.default_rng(seed)
        k = int(n * n * density)
        e = (rng.integers(0, n, size=(k, 2)) if k
             else np.zeros((0, 2), dtype=np.int64))
        g = Graph.from_edges(n, e)
        s = summarize(g, T=3, seed=seed % 97)
        dec = s.decompress()
        for v in range(n):
            assert np.array_equal(s.neighbors(v), dec.neighbors(v).astype(np.int64))

    inner()
