"""Seconds the traced job's pack stage spent filling dense workspace
chunks on the host: the program's `slugger.pack.fill` spans
(`core/merging.py`), which the bank path's shell chunks do not open. A
program without the span finds nothing."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["stages"].get("pack.fill")
