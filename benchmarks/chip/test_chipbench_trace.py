"""The trace reduction: on known intervals, and on a small trace recorded
on one TPU v5e (``fixtures/tiny_v5e.xplane.pb``): one ``jaccard_topj``
Pallas call under a ``bench.stage.merge_round`` host span, 10 ms of sleep,
then one small jitted op under ``bench.stage.exchange``, all inside the
``bench.window`` span."""
from __future__ import annotations

from pathlib import Path

import pytest

from chipbench import trace

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "tiny_v5e.xplane.pb"
KERNEL = "custom-call:tpu_custom_call s32[8,128,16]"


def test_union_and_gaps_on_known_intervals():
    cover = trace.union([(5, 8), (0, 2), (1, 3), (7, 9), (20, 30)], 0, 25)
    assert cover == [[0, 3], [5, 9], [20, 25]]
    assert trace.gaps(cover, 0, 25) == [(3, 5), (9, 20)]


def test_reduce_busy_idle_and_attribution():
    ms = 1e6
    red = trace.reduce({
        "devices": {"/device:TPU:0": [("topj", 10 * ms, 30 * ms),
                                      ("fold", 25 * ms, 40 * ms),
                                      ("topj", 80 * ms, 90 * ms)]},
        "spans": [(trace.WINDOW_SPAN, 0.0, 100 * ms),
                  ("bench.job", 0.0, 100 * ms),
                  ("bench.stage.merge_round", 0.0, 60 * ms),
                  ("bench.stage.exchange", 60 * ms, 100 * ms)]})
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.040)     # 10-40 and 80-90 ms
    assert red["op_seconds"] == pytest.approx({"topj": 0.030, "fold": 0.015})
    # idle: 0-10 and 40-60 ms under merge_round, 60-80 and 90-100 under
    # exchange; the innermost span wins over bench.job
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"bench.stage.merge_round": 0.030, "bench.stage.exchange": 0.030})


def test_reduce_averages_devices_and_needs_device_work():
    ms = 1e6
    red = trace.reduce({"devices": {"a": [("x", 0.0, 10 * ms)],
                                    "b": [("x", 0.0, 30 * ms)]},
                        "spans": [(trace.WINDOW_SPAN, 0.0, 40 * ms)]})
    assert red["busy_s"] == pytest.approx(0.020) and red["devices"] == 2
    assert trace.reduce({"devices": {}, "spans": []}) is None


def test_recorded_chip_trace():
    recorded = trace.load(str(FIXTURE))
    red = trace.reduce(recorded)
    assert red is not None and red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.012079, rel=1e-3)
    # inside the window only the small op's 0.8 us ran on the device: the
    # device's timestamps in this trace read about 1.3 ms earlier than the
    # host's, so the kernel, launched in the window's first millisecond,
    # lands before the window starts
    assert red["busy_s"] == pytest.approx(8.13e-7, rel=1e-3)
    assert 1 - red["busy_s"] / red["window_s"] > 0.99
    idle = dict(red["idle_gaps"])
    assert set(idle) == {"unattributed", "bench.stage.merge_round",
                         "bench.stage.exchange"}
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    # over the device's own extent the Pallas kernel's time is all there
    whole = trace.reduce({"devices": recorded["devices"], "spans": []})
    assert whole["op_seconds"][KERNEL] == pytest.approx(5.8173e-5, rel=1e-3)
    assert whole["device_ops"][0][0] == KERNEL
