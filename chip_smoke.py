"""Chip smoke: drive the summarizer's device path and the summary-serving
path once on a TPU, through the entry points a user calls, and check every
result against an independent reference.

    python chip_smoke.py             # one chip: summarize, bit-identity, serving
    python chip_smoke.py --chips 4   # only the 4-device mesh summarize (T=1)
                                     # and the one-device run it must equal

Phases (one process, one chip unless ``--chips 4``):

* summarize — a Graph500-spec R-MAT (scale 18, edge factor 16, ~4M
  undirected edges) through ``SummarizerEngine(backend="resident", T=2)``
  on one device. The summary must be lossless, the run context and the adjacency
  bank must stay live to the end, no fallback may be recorded, and every
  round op must have run as a compiled (not interpreted) Pallas kernel.
* bit-identity — the 55k serving graph, resident on the chip against the
  ``numpy`` engine on the host: ``parent`` and ``edges`` array-equal.
* serving — the summary packed for serving, 4096 mixed queries through
  ``SummaryQueryServer`` with the ``numpy``, ``jax`` and ``pallas``
  backends; every ``numpy`` answer is checked against the input graph, and
  every ``jax``/``pallas`` answer against ``numpy``.

The last line of stdout is one JSON object naming the device; it is printed
only when every phase passed. Without a TPU the script exits non-zero at
once and prints no result.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

RMAT = dict(scale=18, edge_factor=16, a=0.57, b=0.19, c=0.19, seed=0)
# T=3 took over 10 minutes on one v5e, almost all of it compiling the
# per-chunk round programs; the graph stays at full size and T is cut
T_SUMMARIZE = 2
# the four-chip comparison runs the summarize twice (mesh, one device), so
# it takes one iteration: θ = 0 there, and every merge kind still happens
T_MESH = 1
T_BIT_IDENTITY = 3
N_QUERIES = 4096
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[chip-smoke +{time.perf_counter() - _T0:.1f}s] {msg}", flush=True)


class CompileClock:
    """Wall seconds during which the backend compiler ran or a compiled
    program was read from the persistent cache, from JAX's monitoring events
    (one per compile or read, at its end) — the set-up share of a phase's
    wall time. Compiles on concurrent threads overlap, so the set-up is the
    length of the union of their intervals. Tracing and lowering stay in
    the run share. Cache hits are counted apart: a warm cache shortens the
    set-up without any change to the program."""

    def __init__(self):
        import jax

        self.spans: list = []   # (start, end, is_compile)
        self.saved: list = []   # (end, compile seconds a cache hit saved)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        end = time.perf_counter()
        if event in (_COMPILE_EVENT, _CACHE_READ_EVENT):
            self.spans.append((end - duration, end, event == _COMPILE_EVENT))
        elif event == _CACHE_SAVED_EVENT:
            self.saved.append((end, duration))

    def setup_since(self, t0: float) -> dict:
        spans = sorted((max(a, t0), b, c) for a, b, c in self.spans if b > t0)
        total, reach = 0.0, t0
        for a, b, _ in spans:
            if b > reach:
                total += b - max(a, reach)
                reach = b
        hits = [s for end, s in self.saved if end > t0]
        return dict(setup=total, compiles=sum(c for _, _, c in spans),
                    cache_hits=len(hits), saved=sum(hits))


def one_device_mesh(device):
    """A one-device mesh: the engine then takes its single-device path (run
    context + adjacency bank) on ``device`` even on a multi-chip host."""
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array([device]), ("data",))


def resident_run(g, T: int, mesh, clock: CompileClock):
    """One resident summarize; returns (summary, engine, timings)."""
    from repro.core.engine import SummarizerEngine
    from repro.kernels.bitset_fold import ops

    for cache in (ops._ROUND_CACHE, ops._FOLDC_CACHE):
        cache.clear()
    t0 = time.perf_counter()
    # one thread per core for the merge_round stage: each workspace chunk
    # compiles its own round programs on first use, and the chunks of an
    # iteration compile concurrently (record mode keeps the result
    # independent of the schedule)
    eng = SummarizerEngine(backend="resident", partitions=1, T=T, seed=0,
                           mesh=mesh, workers=os.cpu_count())
    summary = eng.run(g)
    wall = time.perf_counter() - t0
    tm = clock.setup_since(t0)
    tm.update(wall=wall, run=wall - tm["setup"],
              op_keys=list(ops._ROUND_CACHE.keys())
              + list(ops._FOLDC_CACHE.keys()))
    return summary, eng, tm


def check_compiled_kernels(keys) -> None:
    """Every round / fold op the run built was the Pallas kernel, compiled:
    the cache keys carry (…, use_kernel, interpret, mesh) — see
    `kernels/bitset_fold/ops.round_fn` and `fold_counts_fn`."""
    assert keys, "the run built no round op"
    for k in keys:
        use_kernel, interpret = k[-3], k[-2]
        assert use_kernel is True and interpret is False, (
            f"round op ran use_kernel={use_kernel} interpret={interpret}: {k}")


def summarize_phase(dev, clock):
    from repro.graphs import generators

    t0 = time.perf_counter()
    g = generators.rmat(RMAT["scale"], edge_factor=RMAT["edge_factor"],
                        a=RMAT["a"], b=RMAT["b"], c=RMAT["c"],
                        seed=RMAT["seed"])
    log(f"summarize: R-MAT scale {RMAT['scale']} edge factor "
        f"{RMAT['edge_factor']}: n={g.n} m={g.m} "
        f"(generated in {time.perf_counter() - t0:.3f}s)")
    s, eng, tm = resident_run(g, T_SUMMARIZE, one_device_mesh(dev), clock)
    st = eng.stats
    log(f"summarize: T={T_SUMMARIZE} merges={st['merges']}")
    log(f"summarize: wall {tm['wall']:.3f}s = set-up {tm['setup']:.3f}s + run "
        f"{tm['run']:.3f}s; {tm['compiles']} compiles, {tm['cache_hits']} "
        f"persistent-cache hits saving {tm['saved']:.3f}s of compile")
    log("summarize: stages (s) " + json.dumps(
        {k: round(v, 3) for k, v in st.items() if isinstance(v, float)}))
    log(f"summarize: cost {s.cost()} relative size {s.relative_size(g):.6f}")
    tr = st["transfer"]
    log(f"summarize: transfer h2d {tr['bytes_h2d']} B d2h {tr['bytes_d2h']} B "
        f"rounds {tr['rounds']} by phase (B) {json.dumps(tr['phases'])}")
    log(f"summarize: degradations {st['degradations']}")
    assert st["degradations"] == 0, "a fallback was recorded"
    assert eng._run_ctx is not None, "the resident run context was dropped"
    assert eng._run_ctx.bank is not None, "the adjacency bank was not live"
    log("summarize: run context and adjacency bank live to the end")
    check_compiled_kernels(tm["op_keys"])
    log(f"summarize: {len(tm['op_keys'])} cached round/fold ops, all "
        f"use_kernel=True interpret=False")
    t0 = time.perf_counter()
    assert s.validate_lossless(g), "resident summary is not lossless"
    log(f"summarize: validate_lossless True "
        f"({time.perf_counter() - t0:.3f}s on host)")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"summarize: peak HBM {peak} B on {dev.device_kind}")
    return g, s


def bit_identity_phase(dev, clock):
    import numpy as np
    from repro.core.engine import SummarizerEngine
    from repro.graphs.generators import SERVING_GRAPHS

    g = SERVING_GRAPHS["55k"]()
    s_dev, eng, tm = resident_run(g, T_BIT_IDENTITY, one_device_mesh(dev),
                                  clock)
    assert eng.stats["degradations"] == 0, "a fallback was recorded"
    assert eng._run_ctx is not None and eng._run_ctx.bank is not None
    check_compiled_kernels(tm["op_keys"])
    t0 = time.perf_counter()
    s_host = SummarizerEngine(backend="numpy", T=T_BIT_IDENTITY,
                              seed=0).run(g)
    t_host = time.perf_counter() - t0
    assert np.array_equal(s_dev.parent, s_host.parent), "parent differs"
    assert np.array_equal(s_dev.edges, s_host.edges), "edges differ"
    log(f"bit-identity: 55k n={g.n} m={g.m} T={T_BIT_IDENTITY}: resident "
        f"(chip, {tm['wall']:.3f}s incl. {tm['setup']:.3f}s set-up) == numpy "
        f"(host, {t_host:.3f}s): parent and edges array-equal, cost "
        f"{s_dev.cost()}")


def serving_phase(g, s):
    import numpy as np
    from repro.core.query_batch import BACKENDS
    from repro.launch.summary_serve import SummaryQueryServer, make_queries

    packed = s.pack_for_serving()
    queries = make_queries(g.n, N_QUERIES)
    answers = {}
    for backend in BACKENDS:
        server = SummaryQueryServer(packed, backend=backend)
        t0 = time.perf_counter()
        server.run(queries)           # first pass compiles every shape
        t1 = time.perf_counter()
        answers[backend] = server.run(queries)
        dt = time.perf_counter() - t1
        log(f"serving: {backend}: {len(queries)} queries in {dt:.3f}s "
            f"({len(queries) / dt:.1f} q/s, host clock), first pass "
            f"{t1 - t0:.3f}s")
    n_nb = 0
    for q, a in zip(queries, answers["numpy"]):
        # the reference is the input graph itself (the summary is lossless)
        nbr = g.indices[g.indptr[q[1]]:g.indptr[q[1] + 1]]
        if q[0] == "neighbors":
            n_nb += 1
            assert np.array_equal(np.sort(a), np.sort(nbr)), q
        else:
            assert a == bool(np.isin(q[2], nbr)), q
    for backend in BACKENDS:
        for q, a, want in zip(queries, answers[backend], answers["numpy"]):
            if q[0] == "neighbors":
                assert np.array_equal(a, want), (backend, q)
            else:
                assert a == want, (backend, q)
    log(f"serving: {len(queries)} queries ({n_nb} neighbors, "
        f"{len(queries) - n_nb} edge): numpy == input graph, jax and pallas "
        f"== numpy on every answer")


def mesh_phase(devices, clock):
    import numpy as np
    from repro.core.resident import ResidentBitmapArena
    from repro.graphs import generators
    from repro.launch.mesh import make_data_mesh

    g = generators.rmat(RMAT["scale"], edge_factor=RMAT["edge_factor"],
                        a=RMAT["a"], b=RMAT["b"], c=RMAT["c"],
                        seed=RMAT["seed"])
    log(f"mesh: R-MAT n={g.n} m={g.m}, T={T_MESH}")
    mesh = make_data_mesh()
    assert mesh.size == len(devices) == 4, mesh
    # where each chunk arena's bitmap shards live: every device must hold
    # a non-empty slice of the batch axis, not only device 0
    upload = ResidentBitmapArena.from_workspace.__func__
    held = {d.id: 0 for d in devices}

    def recording_upload(cls, *a, **k):
        arena = upload(cls, *a, **k)
        for shard in arena._bits.addressable_shards:
            held[shard.device.id] += shard.data.size
        return arena

    ResidentBitmapArena.from_workspace = classmethod(recording_upload)
    try:
        s_mesh, eng, tm = resident_run(g, T_MESH, mesh, clock)
    finally:
        ResidentBitmapArena.from_workspace = classmethod(upload)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    log(f"mesh: {mesh.size}-device data mesh: wall {tm['wall']:.3f}s = "
        f"set-up {tm['setup']:.3f}s + run {tm['run']:.3f}s, merges "
        f"{eng.stats['merges']}, degradations {eng.stats['degradations']}")
    log(f"mesh: bitmap words held per device {held}; peak bytes per "
        f"device {peaks}")
    assert eng.stats["degradations"] == 0, "a fallback was recorded"
    assert len(held) == 4 and all(held.values()), "a device held no shard"
    s_one, eng1, tm1 = resident_run(g, T_MESH, one_device_mesh(devices[0]),
                                    clock)
    log(f"mesh: one-device run: wall {tm1['wall']:.3f}s = set-up "
        f"{tm1['setup']:.3f}s + run {tm1['run']:.3f}s, degradations "
        f"{eng1.stats['degradations']}")
    assert eng1.stats["degradations"] == 0, "a fallback was recorded"
    assert np.array_equal(s_mesh.parent, s_one.parent), "parent differs"
    assert np.array_equal(s_mesh.edges, s_one.edges), "edges differ"
    assert s_mesh.validate_lossless(g), "mesh summary is not lossless"
    log(f"mesh: 4-device summary == one-device summary (parent and edges "
        f"array-equal), lossless, cost {s_mesh.cost()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-device mesh summarize and the "
                         "one-device run it is compared with")
    args = ap.parse_args(argv)
    try:
        import jax
        backend = jax.default_backend()
    except Exception as e:  # no usable JAX backend at all
        log(f"FAIL: JAX could not start: {e!r}")
        return 1
    if backend != "tpu":
        log(f"FAIL: no TPU (JAX backend is {backend!r})")
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        log(f"FAIL: --chips {args.chips} but JAX sees {len(devices)} device(s)")
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    logging.basicConfig(format="%(asctime)s %(name)s %(message)s")
    logging.getLogger("repro").setLevel(logging.INFO)  # per-iteration lines

    log(f"compile cache: {enable_compile_cache()}")
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"jax {jax.__version__}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(devices[:4], clock)
    else:
        g, s = summarize_phase(dev, clock)
        bit_identity_phase(dev, clock)
        serving_phase(g, s)
    log(f"all phases passed in {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
