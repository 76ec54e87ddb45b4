"""Back-to-back whole summarize jobs on one deployment graph.

Every run does the same work: the traffic's fixed pool of ``job_seeds``,
each of which relabels the configuration's graph and seeds the summarizer,
as one job each. The run's seed draws only the order of the pool. Set-up
generates the pool's graphs, builds the program's CSR, and runs each job
once, which compiles every chunk shape the pool needs (or reads it from the
persistent compilation cache); ``setup_s`` is the time from process start
to the end of those jobs. The window then runs the pool's jobs in that
order, pass after pass, through the same entry point, and closes at the end
of the first whole pass that ends after ``--seconds``. Once it has closed,
the device's peak memory is read, the program's state is dropped, and the
window's jobs of one job seed, the first in the run's order, are compared
with the plain reference (`chipbench.correct`).

Traffic keys: ``backend`` and ``partitions`` for `SummarizerEngine`,
``job_seeds``; the configuration gives ``T``. With ``--trace 1`` the first
job of the window is traced, with a host span around each of the engine's
stages.
"""
from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from chipbench import correct, reference
from chipbench.bench import Outcome, cpu_workers
from chipbench.clock import CompileClock
from chipbench.window import rate, run_window


def _traced_stages(cell):
    """The engine's five default stages, each inside a host span."""
    from repro.core.engine import STAGE_ORDER, SummarizerEngine

    def wrap(name):
        fn = getattr(SummarizerEngine, f"stage_{name}")

        def stage(engine, ctx):
            with cell.tracer.span(f"bench.stage.{name}"):
                return fn(engine, ctx)
        return stage

    return {name: wrap(name) for name in STAGE_ORDER}


def _job(cell, g, seed: int, mesh, workers: int, traced: bool) -> dict:
    from repro.core.engine import SummarizerEngine

    cfg, tr = cell.config, cell.traffic
    t0 = time.perf_counter()
    eng = SummarizerEngine(backend=tr["backend"],
                           partitions=int(tr["partitions"]), T=int(cfg["T"]),
                           seed=seed, mesh=mesh, workers=workers,
                           stages=_traced_stages(cell) if traced else None)
    with (cell.tracer.span("bench.job") if traced
          else contextlib.nullcontext()):
        s = eng.run(g)
    wall = time.perf_counter() - t0
    st = eng.stats
    return {"job_seed": seed, "wall_s": wall,
            "stages": {k: v for k, v in st.items() if isinstance(v, float)},
            "transfer": st["transfer"], "merges": int(st["merges"]),
            "degradations": int(st["degradations"]),
            "output": (np.asarray(s.parent), np.asarray(s.edges))}


def run(cell) -> Outcome:
    import jax
    from jax.sharding import Mesh
    from repro.graphs.csr import Graph

    log = cell.log
    clock = CompileClock()
    pool = [int(s) for s in cell.traffic["job_seeds"]]
    order = [pool[i] for i in
             np.random.default_rng(cell.seed).permutation(len(pool))]
    gen = cell.generator()
    inputs = {s: gen.generate(cell.config, s) for s in order}
    graphs = {s: Graph.from_edges(*inputs[s]) for s in order}
    sizes = {g.m for g in graphs.values()}
    if len(sizes) != 1:
        raise RuntimeError(f"the job seeds' graphs differ in size: {sizes}")
    m = sizes.pop()
    log(f"graph: n={graphs[order[0]].n} m={m}; job seeds in order {order}")
    dev = jax.devices()[0]
    # a one-device mesh: the engine's single-device path (run context and
    # adjacency bank) on this chip
    mesh = Mesh(np.array([dev]), ("data",))
    workers = cpu_workers()
    for s in order:
        first = _job(cell, graphs[s], s, mesh, workers, traced=False)
        log(f"set-up job {s}: {first['wall_s']:.3f}s, "
            f"{first['merges']} merges")
    del first
    setup_s = time.perf_counter() - cell.t_process
    log(f"set-up: {setup_s:.3f}s; compile {clock.between(cell.t_process)}")

    def job(i):
        s = order[i % len(order)]
        if cell.trace and i == 0:
            with cell.tracer.capture():
                return _job(cell, graphs[s], s, mesh, workers, traced=True)
        return _job(cell, graphs[s], s, mesh, workers, traced=False)

    jobs, window_s, (t0, t1) = run_window(job, cell.seconds,
                                          every=len(order))
    in_window = clock.between(t0, t1)
    log(f"window: {len(jobs)} jobs in {window_s:.3f}s "
        f"({[round(j['wall_s'], 3) for j in jobs]}); compile {in_window}")
    for i, j in enumerate(jobs):
        log(f"job {i} ({j['job_seed']}) stages (s): "
            + ", ".join(f"{k} {v:.3f}" for k, v in j["stages"].items()))
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    # the sample compared: every job of the window with the run's first
    # job seed
    checked = order[0]
    outputs = [j.pop("output") + (j["degradations"],) for j in jobs]
    sample = [o for o, j in zip(outputs, jobs) if j["job_seed"] == checked]
    del outputs
    gc.collect()
    t_ref = time.perf_counter()
    n, edges = inputs[checked]
    ref = reference.summarize(n, edges, T=int(cell.config["T"]),
                              seed=checked)
    per_job = correct.compare(sample, ref)
    found = correct.total(per_job)
    failed = sum(not correct.verdict(f) for f in per_job)
    log(f"reference and comparison of {len(sample)} jobs ({checked}): "
        f"{time.perf_counter() - t_ref:.3f}s")

    return Outcome(
        end_to_end={"summarize_edges_per_s": rate(m, len(jobs) - failed,
                                                  window_s),
                    "setup_s": setup_s},
        observations={"jobs": jobs, "traced_job": jobs[0] if cell.trace
                      else None, "window_s": window_s, "edges": m,
                      "compiles_in_window": in_window["compiles"],
                      "peak_hbm_bytes": peak},
        checks=correct.render(found),
        correct=correct.verdict(found),
        attempted=len(jobs), failed=failed,
        memory_peak_bytes=peak)
