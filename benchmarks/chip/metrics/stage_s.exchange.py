"""Seconds the traced job spent in the engine's exchange stage: plan
replay and the adjacency bank's advance, host clock."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["stages"]["exchange"]
