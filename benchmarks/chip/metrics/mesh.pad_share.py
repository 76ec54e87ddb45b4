"""Share of the traced job's mesh-sharded arena rows that are padding, in
%: rows padded to a multiple of the shard count less the real rows, over
all rows, as the program tallies them (`core/resident.py`). A job with no
sharded arena, or of a program without the tally, finds nothing."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    padded = job["stages"].get("mesh.rows_padded")
    if not padded:
        return None
    return 100.0 * (padded - job["stages"]["mesh.rows"]) / padded
