"""Seconds the traced job spent folding each round's accepted pairs into
the resident state (instruction slab and dispatch), summed over the merge
round's threads: the program's `slugger.merge.fold` spans
(`core/merging.py`)."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["stages"].get("merge.fold")
