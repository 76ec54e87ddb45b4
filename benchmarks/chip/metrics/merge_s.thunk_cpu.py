"""Thread-CPU seconds of all merge-round thunks of the traced job: the
`time.thread_time` of the program's `slugger.merge.thunk` spans."""


def read(obs):
    job = obs.get("traced_job")
    if job is None:
        return None
    return job["stages"].get("merge.thunk.cpu")
