"""The comparison refuses its control: the reference with its ranking and
Saving in the configuration's control precision, on the configuration's
graph at a size a test run holds (on the CPU)."""
from __future__ import annotations

from pathlib import Path

import pytest

from chipbench import correct
from chipbench.bench import load_module, read_json
from chipbench.control import control_counts

HERE = Path(__file__).resolve().parent
# the configuration at test size: the same shape, fewer vertices
SMALL = {"graph500": {"scale": 11}}
CASES = [("graph500", 1), ("graph500", 2**31 + 5), ("graph500", 2**33 + 1)]


@pytest.mark.parametrize("config,seed", CASES)
def test_control_is_refused(config, seed):
    cfg = dict(read_json(HERE / "configs" / f"{config}.json"), **SMALL[config])
    gen = load_module(HERE / "generators" / f"{cfg['generator']}.py")
    found = control_counts(cfg, gen.generate, seed)
    assert found["lossless_mismatch"] == 0   # every forest is lossless
    assert found["parent_mismatch"] > 0
    assert not correct.verdict(found)
