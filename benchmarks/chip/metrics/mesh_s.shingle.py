"""Seconds the traced job spent on its sharded shingles: the program's
`slugger.mesh.shingle` spans (`core/distributed.shingle_provider`), each
one rehash's dispatch over the mesh, its download and the host's
root-level minimum. A program without the span finds nothing."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["stages"].get("mesh.shingle")
