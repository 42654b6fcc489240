"""evps: Graphalytics' edges-plus-vertices per second.  Every job of the
window contributes V + E (E = the directed edge slots of the served COO);
the time is all of the window's, from the first submit to the end of the
last job's ``block_until_ready``, gaps between jobs included.  Host
clock."""


def read(run):
    jobs = run.done_jobs
    if not jobs:
        return None
    span = jobs[-1].t_done - jobs[0].t_submit
    return len(jobs) * (run.n_vertices + run.n_edges) / span
