"""The per-layer metric of the device queue sweep: it reads the traced
job's ``merge.device_sweep`` seconds, and finds nothing without a traced
job or in a job of a program that sweeps oversized groups on the host."""
from __future__ import annotations

from pathlib import Path

from chipbench.bench import load_module

READER = Path(__file__).resolve().parent / "metrics" / \
    "merge_s.device_sweep.py"


def test_device_sweep_metric_reads_its_key():
    reader = load_module(READER)
    stages = {"merge.device_sweep": 0.75, "merge.host_sweep": 0.0}
    assert reader.read({"traced_job": {"stages": stages}}) == 0.75
    assert reader.read({"traced_job": None}) is None
    assert reader.read({}) is None
    # the parent program: host sweeps and no device sweep span
    assert reader.read({"traced_job": {"stages": {
        "merge_round": 7.6, "merge.host_sweep": 62.1}}}) is None
