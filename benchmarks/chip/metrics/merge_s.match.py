"""Seconds of host matching and bookkeeping in the traced job's batched
chunks, summed over the merge round's threads: the self time of the
program's `slugger.merge.chunk` spans, their wall time less their arena
build, round trips and folds (`core/merging.py`)."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["stages"].get("merge.chunk.self")
