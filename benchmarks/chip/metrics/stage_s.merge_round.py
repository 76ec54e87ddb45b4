"""Seconds the traced job spent in the engine's merge_round stage: the
per-chunk round kernels and the host sweeps of large groups, host clock."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["stages"]["merge_round"]
