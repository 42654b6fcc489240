"""One run of one benchmark cell: set-up, measured window, check, result.

Everything a cell needs is found by name.  ``BENCHMARK.json`` lists the
cells and metrics; a cell names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/mixes/<traffic>.json``); the mix names its algorithm, whose
reference and comparison are ``bench/checks/<algorithm>.py``; each metric
is read by ``bench/metrics/<metric>.py``.  Adding a configuration, a mix,
a check or a metric is adding files and entries.

The window drives the served entry: the configuration's graph goes into a
``GraphAnalyticsService`` once (``add_graph``), and then one closed-loop
client submits the mix's query back to back, ``submit`` -> ``result`` ->
``block_until_ready``, for ``--seconds``.  Every job submitted inside the
window counts, the last one too, which ends after the window closes.
Once the window has closed and device memory has been read, every
answer of the window is compared with the check's plain reference.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Optional

import jax
import numpy as np
import scipy.sparse as sp

from bench import graphs
from bench import trace as T

ROOT = Path(__file__).resolve().parents[1]
TRACE_DEPTH = 4096
GRAPH_NAME = "snapshot"


class SetupError(RuntimeError):
    """The cell cannot run here (no accelerator, too few chips, a
    configuration that does not hold)."""


@dataclasses.dataclass
class Job:
    """One submit -> result of the closed loop, on the host clock."""

    ticket_id: Optional[int]
    t_submit: float
    t_done: float
    iterations: Optional[int] = None
    variant: Optional[str] = None
    value: object = None            # the answer, on the host
    error: Optional[str] = None

    @property
    def wall_s(self) -> float:
        return self.t_done - self.t_submit


@dataclasses.dataclass
class Run:
    """What a run measured; metric readers take their numbers from it."""

    config: dict
    mix: dict
    n_vertices: int
    n_edges: int
    device_kind: str
    setup_s: float
    jobs: list
    resident_bytes: Optional[int]
    window_peak_bytes: Optional[int]
    programs_loaded: int            # compiled or loaded in the window
    tracer: object = None           # the service's obs.Tracer (traced run)
    profile: Optional[T.Summary] = None

    @property
    def done_jobs(self) -> list:
        return [j for j in self.jobs if j.error is None]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SetupError(f"no workload named {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, cell: str, traced: bool) -> list:
    """The cell's end-to-end metrics, or with ``traced`` its per-layer
    ones: every entry without a ``workloads`` key, and those listing it."""
    entries = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``$JAX_COMPILATION_
    CACHE_DIR``, else ``<checkout>/.jax_cache``), keeping every program
    however fast it compiled: the PageRank runner traces its Pregel
    program anew in every job, and a program under JAX's default one
    second would otherwise compile inside the window."""
    from repro.utils.compile_cache import enable_compile_cache as enable
    cache = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache


def chips_for(cell: dict, require_accelerator: bool) -> list:
    devices = jax.devices()
    if require_accelerator and devices[0].platform == "cpu":
        raise SetupError("JAX found no accelerator; the benchmark measures "
                         "nothing on the host CPU")
    if len(devices) < int(cell["chips"]):
        raise SetupError(f"the cell needs {cell['chips']} chip(s), JAX "
                         f"found {len(devices)}")
    return devices[: int(cell["chips"])]


def memory(devices, key: str) -> Optional[int]:
    vals = [(d.memory_stats() or {}).get(key) for d in devices]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


class CompileCounter:
    """Counts, while armed, the programs JAX compiled and those it loaded
    from the persistent cache instead.  ``pregel.programs_per_job`` reads
    both; a compile inside the window is logged."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.armed = False
        self.requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if self.armed and event == self.BACKEND:
            self.requests += 1

    def _on_event(self, event: str, **_) -> None:
        if self.armed and event == self.CACHE_HIT:
            self.cache_hits += 1

    @property
    def compiles(self) -> int:
        return self.requests - self.cache_hits

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


class MemorySampler:
    """The most device memory held on the fullest chip, read every
    ``interval_s`` by one thread while it runs: the window's own peak.
    Held is ``bytes_in_use`` (live arrays) plus ``bytes_reserved``, which
    the TPU runtime sets aside for a program's temporaries (a PageRank
    superstep's E-sized messages are there, not in ``bytes_in_use``).
    JAX's ``peak_bytes_in_use`` cannot be reset, so it holds the peak of
    set-up too, which moves with how fast the host dispatched there."""

    def __init__(self, devices, interval_s: float = 0.002):
        self.devices = devices
        self.interval_s = interval_s
        self.peak: Optional[int] = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _read(self) -> None:
        held = [s["bytes_in_use"] + s.get("bytes_reserved", 0)
                for s in (d.memory_stats() for d in self.devices)
                if s and "bytes_in_use" in s]
        if held and (self.peak is None or max(held) > self.peak):
            self.peak = max(held)

    def _loop(self) -> None:
        while not self._stop.is_set():
            self._read()
            self._stop.wait(self.interval_s)

    def __enter__(self) -> "MemorySampler":
        self._read()
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()
        self._read()


def one_job(svc, query) -> Job:
    """submit -> result -> block_until_ready, timed; then the answer is
    copied to the host, as the client that asked for it would."""
    t0 = time.perf_counter()
    ticket = None
    try:
        with jax.profiler.TraceAnnotation("bench.submit"):
            ticket = svc.submit(GRAPH_NAME, query)
        with jax.profiler.TraceAnnotation("bench.result"):
            r = svc.result(ticket)
        with jax.profiler.TraceAnnotation("bench.block_until_ready"):
            jax.block_until_ready(r.value)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.fetch"):
            value = jax.device_get(r.value)
    except Exception as e:       # a failed job counts; the loop goes on
        return Job(getattr(ticket, "ticket_id", None), t0,
                   time.perf_counter(), error=repr(e))
    return Job(ticket.ticket_id, t0, t1, r.iterations,
               r.meta.get("variant"), value)


def reference_graph(src: np.ndarray, dst: np.ndarray,
                    n: int) -> sp.csr_matrix:
    """The symmetrized graph from the benchmark's own edges: row u lists
    u's neighbours.  The pairs are distinct, so every entry is 1."""
    rows = np.concatenate([src, dst])
    cols = np.concatenate([dst, src])
    ones = np.ones(rows.shape[0], dtype=np.float32)
    return sp.csr_matrix((ones, (rows, cols)), shape=(n, n))


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             t_start: float, require_accelerator: bool = True,
             root: Path = ROOT, log=None) -> dict:
    """Run the cell once; return the result line's object.  ``root``
    holds ``BENCHMARK.json`` and the ``bench`` directory whose
    configurations, mixes, checks, generators and metric readers the
    cell names."""
    from repro.core import graph as G
    from repro.core.query import GraphQuery
    from repro.core.service import GraphAnalyticsService

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    bench_dir = Path(root) / "bench"
    bench = load_benchmark(root)
    cell = find_cell(bench, workload)
    config = load_json(bench_dir / "configs" / f"{cell['config']}.json")
    mix = load_json(bench_dir / "mixes" / f"{cell['traffic']}.json")
    if mix["loop"] != "closed" or int(mix["clients"]) != 1 \
            or float(mix["think_s"]) != 0:
        raise SetupError("the harness drives one closed-loop client with "
                         "no think time")
    check = graphs.load_plugin("checks", mix["algorithm"], bench_dir)
    readers = [(m, graphs.load_plugin("metrics", m["name"], bench_dir))
               for m in metrics_for(bench, workload, traced)]
    devices = chips_for(cell, require_accelerator)
    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{enable_compile_cache()}")

    # -- set-up: graph, service, one warm-up job of the cell's own query
    marks = [("start", t_start), ("jax", time.perf_counter())]
    n = graphs.n_vertices(config)
    src, dst = graphs.generate(config, seed, bench_dir)
    marks.append(("graph", time.perf_counter()))
    coo = G.build_coo(src, dst, n, symmetrize=bool(config["symmetrize"]))
    marks.append(("build_coo", time.perf_counter()))
    if coo.n_edges != int(config["edge_slots"]):
        raise SetupError(f"the service's graph holds {coo.n_edges} edge "
                         f"slots, the configuration states "
                         f"{config['edge_slots']}")
    svc = GraphAnalyticsService(
        trace_depth=TRACE_DEPTH if traced else 0,
        interactive_threshold_s=float(mix["interactive_threshold_s"]),
        **config["service"])
    svc.add_graph(GRAPH_NAME, coo)
    marks.append(("add_graph", time.perf_counter()))
    query = GraphQuery.of(mix["algorithm"], count_only=bool(mix["count_only"]),
                          **mix["params"])
    warmup = one_job(svc, query)
    if warmup.error is not None:
        raise SetupError(f"the warm-up job failed: {warmup.error}")
    resident = memory(devices, "bytes_in_use")
    marks.append(("warm-up", time.perf_counter()))
    setup_s = marks[-1][1] - t_start
    phases = ", ".join(f"{name} {b - a:.3f}"
                       for (_, a), (name, b) in zip(marks, marks[1:]))
    log(f"device memory after set-up: {resident} in use, peak "
        f"{memory(devices, 'peak_bytes_in_use')}")
    log(f"set-up {setup_s:.3f} s ({phases}): V={n} E={coo.n_edges}; "
        f"warm-up job {warmup.wall_s:.3f} s, {warmup.iterations} "
        f"supersteps, variant {warmup.variant}")
    del coo

    # -- the measured window
    compiles = CompileCounter()
    trace_dir = tempfile.TemporaryDirectory() if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir.name, profiler_options=opts)
    jobs = []
    with MemorySampler(devices) as sampler:
        compiles.armed = True
        t_open = time.perf_counter()
        deadline = t_open + seconds
        while time.perf_counter() < deadline:
            jobs.append(one_job(svc, query))
        compiles.armed = False
        t_close = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    compiles.close()
    peak = memory(devices, "peak_bytes_in_use")
    failed = sum(j.error is not None for j in jobs)
    log(f"window {t_close - t_open:.3f} s: {len(jobs)} jobs, {failed} "
        f"failed; {compiles.compiles} programs compiled and "
        f"{compiles.cache_hits} loaded from the compilation cache in it; "
        f"bytes held at most {sampler.peak} (process peak {peak}); "
        f"walls {[round(j.wall_s, 3) for j in jobs[:50]]}")
    for j in jobs:
        if j.error is not None:
            log(f"job #{j.ticket_id} failed: {j.error}")
        elif j.variant != warmup.variant:
            log(f"job #{j.ticket_id} ran variant {j.variant}, the warm-up "
                f"{warmup.variant}")

    profile = None
    if traced:
        try:
            profile = T.summarize(T.find_xplane(trace_dir.name))
        except ValueError as e:     # no device plane: nothing to read
            log(f"trace: {e}")
        trace_dir.cleanup()

    # -- the check: every answer of the window against the reference,
    # after the program's state is freed
    answers = [(j.value, j.iterations) for j in jobs if j.error is None]
    tracer = svc.tracer
    del svc
    gc.collect()
    t_check = time.perf_counter()
    adj = reference_graph(src, dst, n)
    ref = check.reference(adj, mix["params"])
    numbers = check.readings(answers, ref) if answers else {}
    limits = check.LIMITS
    correct = (failed == 0 and bool(answers)
               and all(numbers.get(k, float("inf")) <= v
                       for k, v in limits.items()))
    log(f"check {time.perf_counter() - t_check:.3f} s over "
        f"{len(answers)} answers")

    run = Run(config=config, mix=mix, n_vertices=n,
              n_edges=int(config["edge_slots"]),
              device_kind=devices[0].device_kind, setup_s=setup_s,
              jobs=jobs, resident_bytes=resident,
              window_peak_bytes=sampler.peak,
              programs_loaded=compiles.requests,
              tracer=tracer, profile=profile)
    metrics = {}
    for m, reader in readers:
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(jobs), "failed": failed,
              "metrics": metrics, "device": device}
    if profile is not None:
        device["busy_s"] = profile.busy_s
        device["window_s"] = profile.window_s
        result["breakdown"] = profile.breakdown()
    result["checks"] = {k: {"value": numbers.get(k), "limit": v}
                        for k, v in limits.items()}
    return result
