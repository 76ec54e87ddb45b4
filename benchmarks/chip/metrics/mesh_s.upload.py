"""Seconds the traced job spent on the sharded uploads of its chunk
arenas, summed over the merge round's threads: the program's
`slugger.mesh.upload` spans (`core/resident.py`), the `device_put` of a
chunk's bits, alive rows and count tensors over the mesh. A program
without the span finds nothing."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["stages"].get("mesh.upload")
