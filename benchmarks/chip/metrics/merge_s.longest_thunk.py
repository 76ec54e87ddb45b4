"""Seconds of the longest single merge-round thunk of the traced job (one
host sweep or one batched chunk): the longest of the program's
`slugger.merge.thunk` spans, a lower bound on the stage's wall time
whatever the thread count."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["stages"].get("merge.thunk.max")
