"""The per-layer metrics that read the program's own spans: each reads its
key of the traced job's stage seconds, and finds nothing without a traced
job, or in a job of a program that has no such span."""
from __future__ import annotations

from pathlib import Path

import pytest

from chipbench.bench import load_module

METRICS = Path(__file__).resolve().parent / "metrics"

SPAN_METRICS = {
    "merge_s.host_sweep": "merge.host_sweep",
    "merge_s.extract": "merge.extract",
    "merge_s.round_wait": "merge.round",
    "merge_s.match": "merge.chunk.self",
    "merge_s.fold": "merge.fold",
    "merge_s.longest_thunk": "merge.thunk.max",
    "merge_s.thunk_cpu": "merge.thunk.cpu",
    "stage_s.setup": "setup",
}


@pytest.mark.parametrize("metric,key", sorted(SPAN_METRICS.items()))
def test_span_metric_reads_its_key(metric, key):
    reader = load_module(METRICS / f"{metric}.py")
    stages = {k: float(i + 1) for i, k in enumerate(SPAN_METRICS.values())}
    assert reader.read({"traced_job": {"stages": stages}}) == stages[key]
    assert reader.read({"traced_job": None}) is None
    assert reader.read({}) is None
    # a program without the span: the stage seconds of the engine alone
    assert reader.read({"traced_job": {"stages": {"merge_round": 7.9}}}) \
        is None
