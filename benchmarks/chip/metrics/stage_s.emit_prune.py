"""Seconds the traced job spent after the merges: emission of the
encoding (`core/slugger.py`) and pruning (`core/pruning.py`), host clock."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["stages"]["emit"] + job["stages"]["prune"]
