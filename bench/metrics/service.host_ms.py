"""service.host_ms: mean host milliseconds per job outside the engine:
the job's submit -> result wall (the benchmark's clock) minus the
service's ``execute`` span for its ticket (``core/obs.py``), i.e. the
planning, admission, queueing and bookkeeping the service adds.  Read
from the traced run's span trees; nothing when the service kept none."""


def read(run):
    if run.tracer is None:
        return None
    host = []
    for job in run.done_jobs:
        trace = run.tracer.trace(job.ticket_id)
        execute = trace.find("execute") if trace is not None else None
        if execute is None or execute.duration_s is None:
            continue
        host.append(job.wall_s - execute.duration_s)
    if not host:
        return None
    return 1e3 * sum(host) / len(host)
