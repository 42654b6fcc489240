"""pregel.supersteps: mean supersteps per job of the window, as the
engine reports them (``QueryResult.iterations``).  An exact count."""


def read(run):
    iters = [j.iterations for j in run.done_jobs if j.iterations is not None]
    if not iters:
        return None
    return sum(iters) / len(iters)
