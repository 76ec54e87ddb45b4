"""Seconds the traced job spent in candidate generation: the engine's
shingle, group and pack stages (`core/engine.py`), host clock."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return sum(job["stages"][k] for k in ("shingle", "group", "pack"))
