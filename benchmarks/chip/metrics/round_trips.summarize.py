"""Ranking round trips (one fused rank and Saving call each) of the traced
job, as the program counts them (`core/transfer.py`)."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["transfer"]["rounds"]
