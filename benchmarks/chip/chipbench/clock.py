"""Compile and cache-read time from JAX's monitoring events.

Every program JAX builds reports a compile event at its end, whether the
backend compiled it or it was read from the persistent compilation cache; a
cache read reports its own event besides. Compiles on concurrent threads
overlap, so the time is the length of the union of their intervals. The
count of programs built inside the measured window is what "nothing
compiles in the window" is checked by.
"""
from __future__ import annotations

import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileClock:
    def __init__(self):
        import jax

        self.spans: list = []   # (start, end, is_compile)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        end = time.perf_counter()
        if event in (COMPILE_EVENT, CACHE_READ_EVENT):
            self.spans.append((end - duration, end, event == COMPILE_EVENT))

    def between(self, t0: float, t1: float | None = None) -> dict:
        """Programs built (``compiles``), how many of them came from the
        cache (``cache_reads``), and the union of their seconds, for events
        that ended inside ``[t0, t1]``."""
        t1 = float("inf") if t1 is None else t1
        spans = sorted((max(a, t0), b, c) for a, b, c in self.spans
                       if t0 < b <= t1)
        total, reach = 0.0, t0
        for a, b, _ in spans:
            if b > reach:
                total += b - max(a, reach)
                reach = b
        compiles = sum(1 for _, _, c in spans if c)
        return {"seconds": total, "compiles": compiles,
                "cache_reads": len(spans) - compiles}
