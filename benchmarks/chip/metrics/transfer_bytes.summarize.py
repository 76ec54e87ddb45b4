"""Bytes the traced job moved between host and device, both ways, as the
program counts them at each dispatch (`core/transfer.py`)."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["transfer"]["bytes_h2d"] + job["transfer"]["bytes_d2h"]
