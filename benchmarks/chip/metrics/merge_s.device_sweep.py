"""Seconds the traced job's merge rounds spent sweeping candidate groups
over 128 members on the device, summed over the stage's threads: the
program's `slugger.merge.device_sweep` spans (`core/merging.py`), each one
sweep program's dispatch and its merge list's download. A program that
sweeps these groups on the host has no such span, and the metric finds
nothing."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["stages"].get("merge.device_sweep")
