"""setup_s: seconds from the start of the process to the end of the
warm-up job: JAX start-up, graph generation, the service's COO build and
``add_graph``, and one job of the cell's own query (which compiles on a
cold cache).  Host clock."""


def read(run):
    return run.setup_s
