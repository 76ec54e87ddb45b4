"""Seconds the traced job's merge rounds spent sweeping candidate groups
over 128 members on the host, summed over the stage's threads: the
program's `slugger.merge.host_sweep` spans (`core/merging.py`)."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["stages"].get("merge.host_sweep")
