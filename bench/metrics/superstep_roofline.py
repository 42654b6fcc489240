"""superstep_roofline: the share of the HBM roofline the Pregel supersteps
reach: the least bytes the window's supersteps must move
(``bench/roofline.py``) over the chip's peak bandwidth, divided by the
Pregel program's device time in the trace.  Percent."""

from bench import roofline

import numpy as np


def read(run):
    if run.profile is None or not run.profile.program_s:
        return None
    algo = run.mix["algorithm"]
    per_step = roofline.superstep_bytes(
        run.n_vertices, run.n_edges,
        state_itemsize=np.dtype(run.config["state"][algo]).itemsize,
        reads_weight=bool(run.mix["superstep"]["reads_weight"]))
    steps = sum(j.iterations or 0 for j in run.done_jobs)
    if not steps:
        return None
    least_s = per_step * steps / roofline.peaks(run.device_kind)[
        "hbm_bytes_per_s"]
    return 100.0 * least_s / run.profile.program_s
