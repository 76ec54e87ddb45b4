"""The measured window of a job loop, and the rate taken over it."""
from __future__ import annotations

import time


def run_window(job, seconds: float, clock=time.perf_counter, every: int = 1):
    """Run ``job(i)`` back to back from i = 0 and close at the end of the
    first job that ends ``seconds`` or more after the start and completes a
    whole pass of ``every`` jobs. Returns the jobs' results and the window's
    length: the whole time, from the first job's start to the last one's
    end."""
    results = []
    t0 = clock()
    while True:
        results.append(job(len(results)))
        t1 = clock()
        if t1 - t0 >= seconds and len(results) % every == 0:
            return results, t1 - t0, (t0, t1)


def rate(work_per_job: float, jobs_ok: int, window_s: float) -> float:
    """Work of the jobs that finished correct, over all of the window."""
    return work_per_job * jobs_ok / window_s
