"""Seconds the traced job spent setting up before its first iteration:
partitioning, the merge state, and the resident run context with its
edge upload and adjacency bank: the program's `slugger.setup` span
(`core/engine.py`)."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["stages"].get("setup")
