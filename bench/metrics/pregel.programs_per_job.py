"""pregel.programs_per_job: programs the window's jobs compiled or loaded
from the persistent compilation cache, per job (JAX's own compile and
cache-hit events).  0 where every job reuses the programs of the one
before; the PageRank runner builds its Pregel program anew in each job,
so there it reads 1, and a job pays a trace and a cache load."""


def read(run):
    jobs = run.done_jobs
    if not jobs:
        return None
    return run.programs_loaded / len(jobs)
