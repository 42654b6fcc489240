"""device.idle_pct: the share of the traced window in which no operation
ran on the device: 1 - (union of the device's busy intervals) / window,
averaged over the cell's chips.  Profiler trace.  Percent."""


def read(run):
    if run.profile is None or not run.profile.window_s:
        return None
    return 100.0 * (1.0 - run.profile.busy_s / run.profile.window_s)
