"""CPU tests of the benchmark: generators, checks, trace reduction, and
whole runs of small cells with the device check skipped."""
