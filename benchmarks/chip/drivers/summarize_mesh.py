"""Back-to-back whole summarize jobs on one deployment graph, each over a
data mesh of the traffic's ``devices`` chips.

`summarize_loop`'s contract (its docstring): the same fixed pool of job
seeds, set-up, window, observations and comparison with the reference. The
one difference is the mesh: every job's engine is handed a 1-D ``data``
mesh of ``devices`` chips, so the resident engine takes its mesh path:
chunk arenas sharded over their group axis, shingles from the sharded
dispatch, no adjacency bank. `summarize_loop`'s ``_job`` and
``_traced_stages`` are loaded from its file and reused.

A run whose engine did not take the mesh path fails with no result: at
once if the program counts no mesh-sharded arena rows, and after any job
whose sharded arena rows read 0 or whose arenas held shards on fewer than
``devices`` chips. ``memory_peak_bytes`` is the largest peak of the mesh's
devices.

Traffic keys: those of `summarize_loop`, and ``devices``.
"""
from __future__ import annotations

import gc
import time
from pathlib import Path

import numpy as np

from chipbench import correct, reference
from chipbench.bench import Outcome, cpu_workers, load_module
from chipbench.clock import CompileClock
from chipbench.window import rate, run_window

LOOP = load_module(Path(__file__).resolve().parent / "summarize_loop.py")


def check_mesh_job(job: dict, devices: int) -> None:
    """Raise unless the job's arenas were sharded, each over ``devices``
    chips (the engine's tallies, `SummarizerEngine.stats`)."""
    st = job["stages"]
    arenas = st.get("mesh.arenas", 0.0)
    if st.get("mesh.rows_padded", 0.0) <= 0 or arenas <= 0:
        raise RuntimeError(f"job {job['job_seed']} built no mesh-sharded "
                           f"arena: the engine took the one-device path")
    per_arena = st.get("mesh.shard_devices", 0.0) / arenas
    if per_arena < devices:
        raise RuntimeError(f"job {job['job_seed']}: arena shards on "
                           f"{per_arena:.2f} devices per arena, not "
                           f"{devices}")


def _peak_bytes(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(cell) -> Outcome:
    import jax
    from jax.sharding import Mesh
    from repro.core import engine
    from repro.graphs.csr import Graph

    log = cell.log
    if "mesh.rows_padded" not in getattr(engine, "COUNT_STATS", ()):
        raise RuntimeError("the program counts no mesh-sharded arena rows, "
                           "so no job can show that it took the mesh path")
    devices = int(cell.traffic["devices"])
    devs = jax.devices()[:devices]
    if len(devs) < devices:
        raise RuntimeError(f"the traffic asks for {devices} devices; JAX "
                           f"sees {len(devs)}")
    mesh = Mesh(np.array(devs), ("data",))
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
    log(f"graph: n={graphs[order[0]].n} m={m}; job seeds in order {order}; "
        f"mesh over {[d.id for d in devs]}")
    workers = cpu_workers()

    def job(s, traced=False):
        out = LOOP._job(cell, graphs[s], s, mesh, workers, traced=traced)
        check_mesh_job(out, devices)
        return out

    for s in order:
        first = job(s)
        st = first["stages"]
        log(f"set-up job {s}: {first['wall_s']:.3f}s, {first['merges']} "
            f"merges; {st['mesh.arenas']:.0f} arenas, rows "
            f"{st['mesh.rows']:.0f} real of {st['mesh.rows_padded']:.0f}, "
            f"shards on {st['mesh.shard_devices'] / st['mesh.arenas']:.2f} "
            f"devices per arena")
    del first
    setup_s = time.perf_counter() - cell.t_process
    log(f"set-up: {setup_s:.3f}s; compile {clock.between(cell.t_process)}")

    def window_job(i):
        s = order[i % len(order)]
        if cell.trace and i == 0:
            with cell.tracer.capture():
                return job(s, traced=True)
        return job(s)

    jobs, window_s, (t0, t1) = run_window(window_job, cell.seconds,
                                          every=len(order))
    in_window = clock.between(t0, t1)
    log(f"window: {len(jobs)} jobs in {window_s:.3f}s "
        f"({[round(j['wall_s'], 3) for j in jobs]}); compile {in_window}")
    for i, j in enumerate(jobs):
        log(f"job {i} ({j['job_seed']}) stages (s): "
            + ", ".join(f"{k} {v:.3f}" for k, v in j["stages"].items()))
    peak = _peak_bytes(devs)

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
