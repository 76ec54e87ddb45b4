"""Seconds the traced job spent building its chunk arenas from the device
adjacency bank, summed over the merge round's threads: the program's
`slugger.merge.extract` spans (`core/merging.py`)."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["stages"].get("merge.extract")
