"""Seconds the traced job's merge rounds waited on the device round op,
from its dispatch until its verdicts were a host array, summed over the
stage's threads: the program's `slugger.merge.round` spans
(`core/resident.py`)."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["stages"].get("merge.round")
