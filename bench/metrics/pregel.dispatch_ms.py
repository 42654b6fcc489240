"""pregel.dispatch_ms: host milliseconds per job in the Pregel runners'
``pregel.dispatch`` spans, from the jit-cache lookup to the return of
the program call: a program's trace and its compile or cache load when
the cache misses, the call's dispatch when it hits.  The program emits
each span to the service's tracer (``obs.emit``) with its
``time.perf_counter`` ends, the same two clock reads as its profiler
annotation; the spans that start inside the window count.  Nothing when
the program emits none."""

DISPATCH = "pregel.dispatch"


def read(run):
    jobs = run.done_jobs
    if run.tracer is None or not jobs:
        return None
    lo, hi = jobs[0].t_submit, jobs[-1].t_done
    spans = [attrs for _, kind, attrs in list(run.tracer.events)
             if kind == DISPATCH and lo <= attrs["t0"] <= hi]
    if not spans:
        return None
    return 1e3 * sum(a["t1"] - a["t0"] for a in spans) / len(jobs)
