"""The per-layer metrics of the four-chip cell: each reads the traced
job's seconds of a mesh span, or its tally of sharded arena rows, and
finds nothing without a traced job, or in a job of a program that has no
such span or tally."""
from __future__ import annotations

from pathlib import Path

import pytest

from chipbench.bench import load_module

METRICS = Path(__file__).resolve().parent / "metrics"

SPAN_METRICS = {
    "mesh_s.shingle": "mesh.shingle",
    "mesh_s.upload": "mesh.upload",
    "mesh_s.pack_fill": "pack.fill",
}


@pytest.mark.parametrize("metric,key", sorted(SPAN_METRICS.items()))
def test_mesh_span_metric_reads_its_key(metric, key):
    reader = load_module(METRICS / f"{metric}.py")
    stages = {k: float(i + 1) for i, k in enumerate(SPAN_METRICS.values())}
    assert reader.read({"traced_job": {"stages": stages}}) == stages[key]
    assert reader.read({"traced_job": None}) is None
    assert reader.read({}) is None
    # the parent program: the engine's stages and no mesh span
    assert reader.read({"traced_job": {"stages": {
        "merge_round": 7.6, "merge.host_sweep": 62.1}}}) is None


def test_pad_share_reads_the_row_tallies():
    reader = load_module(METRICS / "mesh.pad_share.py")
    stages = {"mesh.rows": 49.0, "mesh.rows_padded": 92.0}
    assert reader.read({"traced_job": {"stages": stages}}) == \
        pytest.approx(100.0 * 43 / 92)
    assert reader.read({"traced_job": {"stages": {
        "mesh.rows": 64.0, "mesh.rows_padded": 64.0}}}) == 0.0
    assert reader.read({"traced_job": None}) is None
    assert reader.read({}) is None
    # a one-device job tallies no sharded row; the parent has no tally
    assert reader.read({"traced_job": {"stages": {
        "mesh.rows": 0.0, "mesh.rows_padded": 0.0}}}) is None
    assert reader.read({"traced_job": {"stages": {
        "merge_round": 7.6}}}) is None
