"""peak_hbm_bytes: the most device memory held during the window, on the
fullest chip of the cell: ``bytes_in_use`` plus ``bytes_reserved`` (the
runtime's reservation for program temporaries), read every 2 ms from the
window's first submit to its last job's end (``harness.MemorySampler``).
It decides the largest snapshot a chip can serve.  Set-up's transients
are not in it: JAX's process-long ``peak_bytes_in_use`` holds those, and
reads 1.02e9 or 1.56e9 bytes on kron-s20.pagerank as the host dispatched
set-up slower (compiling) or faster (programs from the cache)."""


def read(run):
    return run.window_peak_bytes
