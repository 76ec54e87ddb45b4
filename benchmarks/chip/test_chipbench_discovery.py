"""A later change adds a configuration, a traffic mix, a driver and a
metric as new files plus a BENCHMARK.json entry; the harness finds each by
name and no file that was there before is edited."""
from __future__ import annotations

import hashlib
import json
import shutil
from pathlib import Path

from chipbench import bench

HERE = Path(__file__).resolve().parent

NEW_DRIVER = '''
from chipbench.bench import Outcome


def run(cell):
    n, edges = cell.generator().generate(cell.config, cell.seed)
    return Outcome(end_to_end={"things_per_s": float(len(edges)),
                               "setup_s": 1.0},
                   observations={"things": len(edges), "n": n},
                   checks={"wrong": {"value": 0, "limit": 0}},
                   correct=True, attempted=1, failed=0)
'''
NEW_GENERATOR = '''
import numpy as np


def generate(cfg, seed):
    n = int(cfg["n"])
    return n, np.stack([np.arange(n - 1), np.arange(1, n)], axis=1)
'''
NEW_METRIC = '''
def read(obs):
    return obs["things"] / obs["n"]
'''


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_cell_files_are_found_by_name(tmp_path):
    bdir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, bdir,
                    ignore=shutil.ignore_patterns("__pycache__", "*.pyc"))
    shutil.copy(HERE.parents[1] / "BENCHMARK.json", tmp_path)
    before = _digest(tmp_path)

    (bdir / "configs" / "path64.json").write_text(json.dumps(
        {"name": "path64", "generator": "path", "n": 64}))
    (bdir / "generators" / "path.py").write_text(NEW_GENERATOR)
    (bdir / "traffic" / "count.json").write_text(json.dumps(
        {"driver": "count_edges"}))
    (bdir / "drivers" / "count_edges.py").write_text(NEW_DRIVER)
    (bdir / "metrics" / "things_per_node.count.py").write_text(NEW_METRIC)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "path64", "source": "a path graph",
                            "file": "benchmarks/chip/configs/path64.json",
                            "reduced": [], "why": "a new deployment"})
    spec["workloads"].append({"name": "path64.count", "config": "path64",
                              "traffic": "count", "chips": 1,
                              "why": "a new traffic mix"})
    spec["end_to_end"].append({"name": "things_per_s", "unit": "things/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["path64.count"]})
    spec["per_layer"].append({"name": "things_per_node.count",
                              "unit": "count", "better": "lower",
                              "source": "program_counter", "layer": "x",
                              "moves": "things_per_s",
                              "workloads": ["path64.count"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _digest(tmp_path)
    changed = [k for k, v in before.items() if after.get(k) != v]
    assert changed == ["BENCHMARK.json"]

    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    for trace, want in ((False, {"things_per_s": 63.0, "setup_s": 1.0}),
                        (True, {"things_per_node.count": 63 / 64})):
        cell = bench.resolve(tmp_path, "path64.count", 3, 0.0, trace, 0.0,
                             bench_dir=bdir)
        out = bench.run_cell(cell, device)
        assert {k: v["value"] for k, v in out["metrics"].items()} == want
        assert out["correct"] is True
        assert list(out)[-1] == "checks"
