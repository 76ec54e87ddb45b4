"""Named spans inside the summarizer, on the profiler's clock.

`span(name)` times one stretch of the program at the boundary where its
work happens: the engine's stages, set-up, the exchange's plan replay and
bank advance, and inside the merge round the host and device sweeps of
oversized groups, the batched chunks, the bank extraction, the device round trips and
the folds. Each span

* opens ``jax.profiler.TraceAnnotation("slugger.<name>")`` when the program
  has loaded ``jax.profiler``, so with a profiler session running the span
  lands in the trace on the same clock as the device's operations (with
  none running the annotation is one cheap check; without jax it is
  skipped);
* adds its wall seconds (`time.perf_counter`), its thread-CPU seconds
  (`time.thread_time`) and its self seconds to the module's `GLOBAL`
  `SpanTotals`. Self seconds are wall minus the child spans opened on the
  same thread inside it.

Spans are always on, like the stage timers they replace, and open a few
hundred times per job: never per row or per group inside a round. A span
never synchronises the device; it ends where the code it wraps ends.

`SpanTotals` follows `core/transfer.TransferCounter`: one lock, monotonic
totals, and ``snapshot`` / ``delta_since`` so the engine can report one
job's spans (`SummarizerEngine.stats`). ``max`` in a delta is the longest
single span closed inside the interval.

`COUNTS.add(name, n)` adds to a plain integer tally (same lock
discipline, same snapshot and delta), for what the program counts rather
than times: the rows of the mesh-sharded arenas and the devices holding
their shards.
"""
from __future__ import annotations

import bisect
import sys
import threading
import time

PREFIX = "slugger."

_FIELDS = ("count", "wall", "cpu", "self")


class SpanTotals:
    """Per-name count, wall, thread-CPU and self seconds, and the longest
    single span (monotonic; snapshot + delta). Every mutator holds the lock:
    the merge round's thunks close spans from a thread pool."""

    __slots__ = ("_totals", "_longest", "_lock")

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with self._lock:
            self._totals = {}
            # per name, (sequence number, seconds) of the spans no later
            # span outlasted: seconds fall as sequence numbers rise, so the
            # longest span after any point is the first entry past it
            self._longest = {}

    def add(self, name: str, wall: float, cpu: float, self_s: float):
        with self._lock:
            tot = self._totals.get(name)
            if tot is None:
                tot = self._totals[name] = {f: 0 if f == "count" else 0.0
                                            for f in _FIELDS}
                self._longest[name] = []
            tot["count"] += 1
            tot["wall"] += wall
            tot["cpu"] += cpu
            tot["self"] += self_s
            longest = self._longest[name]
            while longest and longest[-1][1] <= wall:
                longest.pop()
            longest.append((tot["count"], wall))

    def snapshot(self) -> dict:
        with self._lock:
            return {name: dict(tot, max=self._longest[name][0][1])
                    for name, tot in self._totals.items()}

    def delta_since(self, snap: dict) -> dict:
        """Totals of the spans closed since ``snap``, per name with at
        least one; ``max`` is the longest of them."""
        out = {}
        with self._lock:
            for name, tot in self._totals.items():
                base = snap.get(name)
                n0 = base["count"] if base else 0
                if tot["count"] == n0:
                    continue
                d = {f: tot[f] - (base[f] if base else 0) for f in _FIELDS}
                longest = self._longest[name]
                i = bisect.bisect_right(longest, (n0, float("inf")))
                d["max"] = longest[i][1] if i < len(longest) else 0.0
                out[name] = d
        return out


GLOBAL = SpanTotals()

_local = threading.local()


class span:
    """``with span("merge.round"): ...`` — one timed, annotated span; its
    wall seconds are on ``.wall`` once it has closed."""

    __slots__ = ("name", "totals", "wall", "_children", "_ann", "_w0", "_c0")

    def __init__(self, name: str, totals: SpanTotals = GLOBAL):
        self.name = name
        self.totals = totals
        self.wall = 0.0
        self._children = 0.0

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(self)
        profiler = sys.modules.get("jax.profiler")
        self._ann = (None if profiler is None
                     else profiler.TraceAnnotation(PREFIX + self.name))
        if self._ann is not None:
            self._ann.__enter__()
        self._c0 = time.thread_time()
        self._w0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self._w0
        cpu = time.thread_time() - self._c0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = _local.stack
        stack.pop()
        if stack:
            stack[-1]._children += wall
        self.wall = wall
        self.totals.add(self.name, wall, cpu, wall - self._children)
        return False


class Counts:
    """Per-name integer tallies (monotonic; snapshot + delta)."""

    __slots__ = ("_totals", "_lock")

    def __init__(self):
        self._lock = threading.Lock()
        self._totals = {}

    def add(self, name: str, n: int):
        with self._lock:
            self._totals[name] = self._totals.get(name, 0) + int(n)

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._totals)

    def delta_since(self, snap: dict) -> dict:
        """Tallies added since ``snap``, per name that grew."""
        now = self.snapshot()
        return {name: v - snap.get(name, 0) for name, v in now.items()
                if v != snap.get(name, 0)}


COUNTS = Counts()
