"""Harness: one cell of ``BENCHMARK.json``, found by name, run once.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own and is found by its name:

    configs/<config>.json      the deployment (its ``file`` in BENCHMARK.json)
    generators/<generator>.py  ``generate(cfg, seed) -> (n, edges)``
    traffic/<traffic>.json     the job's parameters; ``driver`` names
    drivers/<driver>.py        ``run(cell) -> Outcome``
    metrics/<metric>.py        ``read(obs) -> float | None``

so a later cell, traffic mix or metric is a new file plus an entry in
``BENCHMARK.json``, with no existing file edited.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import re
import shutil
import sys
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
TRACE_DIR = ".bench_trace"  # inside the checkout, listed in .gitignore
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def load_module(path: Path):
    """Import one file by path under a private module name (names may hold
    dots, so the import system's own lookup cannot find them)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = "chipbench_" + re.sub(r"\W", "_", str(path.relative_to(
        path.parents[1])))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _checked(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload with everything it names, resolved."""

    root: Path
    bench_dir: Path
    bench: dict
    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float
    log: object = print
    tracer: object = None

    @property
    def name(self) -> str:
        return self.workload["name"]

    def generator(self):
        return load_module(self.bench_dir / "generators"
                           / f"{_checked(self.config['generator'])}.py")

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list:
        return [m for m in self.bench["per_layer"]
                if self.name in m.get("workloads", [self.name])]


@dataclass
class Outcome:
    """What a driver hands back: the end-to-end values it measured, the
    observations the per-layer readers read, and the comparison."""

    end_to_end: dict
    observations: dict
    checks: dict
    correct: bool
    attempted: int
    failed: int
    memory_peak_bytes: int | None = None


def resolve(root: Path, workload: str, seed: int, seconds: float,
            trace: bool, t_process: float, log=print,
            bench_dir: Path = BENCH_DIR) -> Cell:
    bench = read_json(root / "BENCHMARK.json")
    wl = [w for w in bench["workloads"] if w["name"] == workload]
    if not wl:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    wl = wl[0]
    cfgs = [c for c in bench["configs"] if c["name"] == wl["config"]]
    if not cfgs:
        raise KeyError(f"workload {workload!r} names no known config")
    config = read_json(root / cfgs[0]["file"])
    traffic = read_json(bench_dir / "traffic"
                        / f"{_checked(wl['traffic'])}.json")
    return Cell(root=root, bench_dir=bench_dir, bench=bench, workload=wl,
                config=config, traffic=traffic, seed=seed, seconds=seconds,
                trace=trace, t_process=t_process, log=log)


class Tracer:
    """Runs the JAX profiler around one span of the window and reduces the
    trace it writes. Off (a no-op) unless the run was asked to trace."""

    def __init__(self, enabled: bool, logdir: Path):
        self.enabled = enabled
        self.logdir = logdir
        self.reduced = None

    @contextlib.contextmanager
    def capture(self):
        if not self.enabled:
            yield
            return
        import jax

        from chipbench import trace

        # the trace is reduced and deleted in the run that wrote it: only
        # its own file is read, and nothing of it stays on disk
        shutil.rmtree(self.logdir, ignore_errors=True)
        jax.profiler.start_trace(str(self.logdir))
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()
        path = trace.newest_xplane(str(self.logdir))
        if path is not None:
            self.reduced = trace.reduce(trace.load(path))
        shutil.rmtree(self.logdir, ignore_errors=True)

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


def read_per_layer(cell: Cell, obs: dict) -> dict:
    """Each per-layer metric the cell reports, from its own reader; a
    reader that finds nothing returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer():
        reader = load_module(cell.bench_dir / "metrics"
                             / f"{_checked(m['name'])}.py")
        value = reader.read(obs)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, device: dict) -> dict:
    """Drive the cell's traffic and assemble the result line."""
    driver = load_module(cell.bench_dir / "drivers"
                         / f"{_checked(cell.traffic['driver'])}.py")
    cell.tracer = Tracer(cell.trace, cell.root / TRACE_DIR / cell.name)
    out = driver.run(cell)
    device = dict(device, memory_peak_bytes=out.memory_peak_bytes)
    result = {"correct": bool(out.correct), "attempted": int(out.attempted),
              "failed": int(out.failed)}
    if cell.trace:
        obs = dict(out.observations, trace=cell.tracer.reduced,
                   peaks=read_json(cell.bench_dir / "peaks.json").get(
                       device["kind"]))
        result["metrics"] = read_per_layer(cell, obs)
        red = cell.tracer.reduced
        if red is not None:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    else:
        result["metrics"] = {
            m["name"]: {"value": out.end_to_end[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end() if m["name"] in out.end_to_end}
    result["device"] = device
    result["checks"] = out.checks
    return result


def device_info(chips: int) -> dict:
    """The platform JAX runs on; raises unless it is a TPU with at least
    ``chips`` devices, of a kind the table of peaks (``peaks.json``)
    holds."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips; JAX sees "
                           f"{len(devs)}")
    kind = devs[0].device_kind
    if kind not in read_json(BENCH_DIR / "peaks.json"):
        raise RuntimeError(f"no peaks for device kind {kind!r} in peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": chips}


def cpu_workers() -> int:
    return len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity") \
        else (os.cpu_count() or 1)


def print_checks(checks: dict) -> None:
    """The compared numbers beside their limits, as the last stderr lines."""
    for name, c in checks.items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
