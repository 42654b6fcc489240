"""pregel.superstep_ms: device milliseconds per superstep of the Pregel
program: the device time of its executions in the traced window divided
by the supersteps the window's jobs ran.  Profiler trace."""


def read(run):
    if run.profile is None or not run.profile.program_s:
        return None
    steps = sum(j.iterations or 0 for j in run.done_jobs)
    if not steps:
        return None
    return 1e3 * run.profile.program_s / steps
