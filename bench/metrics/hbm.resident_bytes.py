"""hbm.resident_bytes: the device's ``bytes_in_use`` after the warm-up job
and before the window: the graph and the derived state the engines keep
between jobs (edge shards, normalized copies).  Moves peak_hbm_bytes."""


def read(run):
    return run.resident_bytes
