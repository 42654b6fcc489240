"""The chip benchmark of the graph service: ``python bench/run.py``.

See ``BENCHMARK.json`` at the root of the repository for the cells and
metrics, and ``harness.py`` for how a run goes.
"""
