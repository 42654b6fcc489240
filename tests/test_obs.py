"""End-to-end observability: span traces, superstep profiles, and the
planner's estimate-vs-actual feedback loop (ISSUE 10).

The acceptance bar: every ticket of a drained mixed-tier workload has a
complete span tree (admission, full plan-candidate table, queue wait,
attempts, superstep counters, resolution); the hard lifecycles —
retry→success, dead-letter with the exception chain, fused groups
sharing one execute span, spill recording both placements — all
materialize in the tree; the Chrome trace export validates against the
trace-event schema; ``metrics_text()`` round-trips ``metrics()``; and
tracing never changes a single result byte.
"""
import collections
import dataclasses
import functools
import glob
import itertools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as G
from repro.core import obs
from repro.core import planner as P
from repro.core import pools as PL
from repro.core import pregel
from repro.core import registry as R
from repro.core.algorithms import traversal
from repro.core.engines import LocalEngine
from repro.core.partition import partition_1d
from repro.core.query import GraphQuery
from repro.core.runtime import LatencyHistogram, RetryPolicy
from repro.core.service import GraphAnalyticsService
from repro.data import synthetic as S
from repro.kernels.segment_runs import segment_runs_pallas

N = 200


@pytest.fixture(scope="module")
def graph():
    src, dst = S.user_follow_graph(N, 4.0, seed=7)
    return G.build_coo(src, dst, N)


FLAKY = "_obs_flaky"


@pytest.fixture()
def flaky_algorithm():
    R.register(R.AlgorithmDef(
        name=FLAKY,
        run=lambda eng, tag=0: (np.arange(8, dtype=np.float64) + tag, None),
        params=(R.Param("tag", default=0),),
        engines=("local",),
        doc="observability-harness flaky algorithm",
    ), replace=True)
    yield FLAKY
    R.uninstall_fault(None)
    R.unregister(FLAKY)


def _traced_service(graph, **kw):
    kw.setdefault("trace_depth", 32)
    svc = GraphAnalyticsService(**kw)
    svc.add_graph("g", graph)
    return svc


def _bits(v):
    if isinstance(v, dict):
        return b"{" + b";".join(
            str(k).encode() + b"=" + _bits(v[k]) for k in sorted(v)) + b"}"
    if isinstance(v, (tuple, list)):
        return b"(" + b";".join(_bits(x) for x in v) + b")"
    return np.asarray(v).tobytes()


# ---------------------------------------------------------------------------
# The span tree
# ---------------------------------------------------------------------------

def test_span_tree_full_lifecycle(graph):
    """submit → admission → plan → queue-wait → attempt/execute →
    resolve, every span present and closed, wait measured."""
    svc = _traced_service(graph)
    t = svc.submit("g", GraphQuery.bfs([0]))
    svc.result(t)
    tr = svc.tracer.trace(t.ticket_id)
    for name in ("ticket", "submit", "admission", "plan", "queue-wait",
                 "attempt", "execute", "resolve"):
        span = tr.find(name)
        assert span is not None, name
        assert span.t1 is not None, name
    assert tr.root.attrs["status"] == "done"
    qw = tr.find("queue-wait")
    assert qw.attrs["wait_s"] == pytest.approx(qw.duration_s)
    adm = tr.find("admission")
    assert adm.attrs["tier"] == t.tier
    assert adm.attrs["est_s"] == pytest.approx(t.est_s)
    text = svc.explain(t)
    for needle in ("ticket #", "admission", "queue-wait", "attempt",
                   "resolve", "status=done"):
        assert needle in text


def test_plan_span_times_the_planner_call(graph):
    """The plan span is the planner call, read at the clock reads that
    bound it inside submit; admission is the instant the ticket was
    queued; submit spans the whole call."""
    ticks = itertools.count()
    svc = _traced_service(graph, tracer=obs.Tracer(
        trace_depth=8, clock=lambda: float(next(ticks))))
    t = svc.submit("g", GraphQuery.bfs([0]))
    tr = svc.tracer.trace(t.ticket_id)
    submit, plan, adm = (tr.find(n) for n in ("submit", "plan",
                                              "admission"))
    # reads in order: submit starts, plan starts, plan ends, queued
    assert tr.root.t0 == submit.t0
    assert (plan.t0, plan.t1) == (submit.t0 + 1, submit.t0 + 2)
    assert adm.t0 == adm.t1 == submit.t1 == plan.t1 + 1
    svc.result(t)


def test_plan_span_records_all_candidates(graph):
    """The plan span carries the planner's *full* table — every
    (engine, variant) the legacy chooser costed, exactly one chosen,
    and the chosen row is the plan that actually ran."""
    svc = _traced_service(graph)
    t = svc.submit("g", GraphQuery.bfs([0]))
    plan_span = svc.tracer.trace(t.ticket_id).find("plan")
    cands = plan_span.attrs["candidates"]
    # bfs registers 3 variants x 2 engines
    assert len(cands) == 6
    assert sum(c["chosen"] for c in cands) == 1
    chosen = next(c for c in cands if c["chosen"])
    assert chosen["engine"] == t.plan.engine
    assert chosen["variant"] == t.plan.variant
    assert chosen["est_s"] == min(c["est_s"] for c in cands
                                  if c["feasible"])
    losers = [c for c in cands if not c["chosen"]]
    assert losers and all(c["est_s"] >= chosen["est_s"] for c in losers
                          if c["feasible"])
    text = svc.explain(t)
    assert "<- chosen" in text
    assert "vs chosen" in text          # losers annotated with the gap


def test_plan_candidates_span_pools(graph):
    """On a poolset the table enumerates (pool, engine) pairs with the
    transfer term split out, and infeasible rows say why."""
    pools = PL.PoolSet([
        PL.DevicePool("onprem"),
        PL.DevicePool("cloud", compute_scale=0.5),
    ])
    svc = GraphAnalyticsService(pools=pools, trace_depth=8)
    svc.add_graph("g", graph, pools=["onprem"])   # resident on one pool
    t = svc.submit("g", GraphQuery.pagerank())
    cands = svc.tracer.trace(t.ticket_id).find("plan").attrs["candidates"]
    assert {c["pool"] for c in cands} == {"onprem", "cloud"}
    chosen = next(c for c in cands if c["chosen"])
    assert chosen["pool"] == t.plan.pool
    nonresident = [c for c in cands if c["pool"] == "cloud"]
    assert any(c["transfer_s"] > 0 for c in nonresident)
    for c in cands:
        assert c["est_s"] == pytest.approx(c["compute_s"]
                                           + c["transfer_s"])


def test_incremental_mode_candidates_and_explain(graph):
    """A lineage-seeded ticket's table includes the mode rows the
    pricer weighed (incremental chosen vs the full recompute), and
    explain() shows the incremental routing."""
    sym = G.build_coo(np.asarray(graph.src)[: graph.n_edges],
                      np.asarray(graph.dst)[: graph.n_edges],
                      N, symmetrize=True)
    svc = GraphAnalyticsService(trace_depth=8)
    svc.add_snapshot("g", sym, as_of=0)
    q = GraphQuery.of("connected_components")
    svc.call("g", q, as_of=0)                  # the parent seed
    svc.add_snapshot("g", as_of=1, added=[[0, 7], [7, 0]])
    t = svc.submit("g", q)
    assert t.plan.mode == "incremental"
    cands = svc.tracer.trace(t.ticket_id).find("plan").attrs["candidates"]
    modes = {c["mode"] for c in cands}
    assert "incremental" in modes
    chosen = next(c for c in cands if c["chosen"])
    assert chosen["mode"] == "incremental"
    svc.drain()
    text = svc.explain(t)
    assert "mode=incremental" in text
    assert "incremental" in text and "<- chosen" in text


# ---------------------------------------------------------------------------
# Hard lifecycles
# ---------------------------------------------------------------------------

def test_retry_then_success_attempt_spans(graph, flaky_algorithm):
    """2 injected failures then success: three attempt spans, the
    failed ones carrying the error, plus a retry event per backoff."""
    svc = _traced_service(
        graph, interactive_threshold_s=0.0,
        retry=RetryPolicy(max_attempts=3, base_s=1e-4, cap_s=1e-3))
    R.install_fault(FLAKY, R.FailNTimes(2))
    t = svc.submit("g", GraphQuery.of(FLAKY))
    svc.drain()
    assert t.status == "done"
    tr = svc.tracer.trace(t.ticket_id)
    attempts = tr.find_all("attempt")
    assert [a.attrs["attempt"] for a in attempts] == [1, 2, 3]
    assert "error" in attempts[0].attrs and "error" in attempts[1].attrs
    assert "error" not in attempts[2].attrs
    retries = [(name, attrs) for (_, name, attrs) in tr.root.events
               if name == "retry"]
    assert [a["after_attempt"] for _, a in retries] == [1, 2]
    assert all(a["sleep_s"] >= 1e-4 for _, a in retries)
    assert tr.root.attrs["status"] == "done"


def test_dead_letter_exception_chain_on_final_attempt(graph,
                                                      flaky_algorithm):
    """Dead-letter: the final attempt span carries the full __cause__
    chain (one entry per attempt), and the resolve span says so."""
    svc = _traced_service(
        graph, interactive_threshold_s=0.0,
        retry=RetryPolicy(max_attempts=3, base_s=1e-4, cap_s=1e-3))
    R.install_fault(FLAKY, R.FailAlways())
    t = svc.submit("g", GraphQuery.of(FLAKY))
    svc.drain()
    assert t.status == "dead-letter"
    tr = svc.tracer.trace(t.ticket_id)
    last = tr.find_all("attempt")[-1]
    assert len(last.attrs["error_chain"]) == 3
    assert all("FaultInjected" in entry
               for entry in last.attrs["error_chain"])
    resolve = tr.find("resolve")
    assert resolve.attrs["status"] == "dead-letter"
    assert "error" in resolve.attrs
    assert tr.root.attrs["status"] == "dead-letter"
    text = svc.explain(t)
    assert "cause[0]" in text and "cause[2]" in text


def test_fused_group_shares_one_execute_span(graph):
    """K fused tickets point at the SAME execute span (one execution,
    K tickets), which carries one per-ticket child each."""
    svc = _traced_service(graph, interactive_threshold_s=0.0)
    ts = [svc.submit("g", GraphQuery.bfs([s])) for s in (0, 5, 9)]
    svc.drain()
    execs = [svc.tracer.trace(t.ticket_id).find("execute") for t in ts]
    assert len({id(e) for e in execs}) == 1       # the same Span object
    assert len({e.span_id for e in execs}) == 1
    ex = execs[0]
    assert ex.attrs["fused"] is True
    assert ex.attrs["batch_size"] == len(ts)
    assert ex.attrs["group"] == [t.ticket_id for t in ts]
    members = [c for c in ex.children if c.name == "ticket"]
    assert [c.attrs["ticket_id"] for c in members] \
        == [t.ticket_id for t in ts]
    assert [c.attrs["index"] for c in members] == [0, 1, 2]
    assert "superstep" in ex.attrs                # profiled once, shared


def test_spill_records_both_placements(graph):
    """A spilled ticket's plan span keeps the original placement next
    to the spill target — where the planner wanted it AND where it
    actually went."""
    svc = GraphAnalyticsService(
        pools=PL.PoolSet([PL.DevicePool("onprem", capacity=1),
                          PL.DevicePool("cloud", capacity=16)]),
        interactive_threshold_s=0.0, trace_depth=16)
    svc.add_graph("g", graph)
    ts = [svc.submit("g", GraphQuery("bfs", params={"sources": (i,)}))
          for i in range(3)]
    assert [t.pool for t in ts] == ["onprem", "cloud", "cloud"]
    kept = svc.tracer.trace(ts[0].ticket_id).find("plan")
    assert "spilled" not in kept.attrs
    spilt = svc.tracer.trace(ts[1].ticket_id).find("plan")
    assert spilt.attrs["spilled"] is True
    assert spilt.attrs["original_placement"]["pool"] == "onprem"
    assert spilt.attrs["pool"] == "cloud"
    chosen = next(c for c in spilt.attrs["candidates"] if c["chosen"])
    assert chosen["pool"] == "cloud"
    svc.drain()
    text = svc.explain(ts[1])
    assert "spilled=True" in text and "original_placement" in text


def test_cache_hit_skips_execution_spans(graph):
    """A cache-served ticket resolves with a cache-hit event and no
    attempt span — and the cached result never claims the superstep
    counters of the run that populated it."""
    svc = _traced_service(graph, interactive_threshold_s=0.0)
    a = svc.submit("g", GraphQuery.bfs([3]))
    svc.drain()
    b = svc.submit("g", GraphQuery.bfs([3]))
    svc.drain()
    assert "superstep" in svc.result(a).meta
    rb = svc.result(b)
    assert rb.meta.get("cache") == "hit"
    assert "superstep" not in rb.meta
    tr = svc.tracer.trace(b.ticket_id)
    assert tr.find("attempt") is None
    assert any(name == "cache-hit" for (_, name, _) in tr.root.events)
    assert tr.root.attrs["status"] == "done"


# ---------------------------------------------------------------------------
# Superstep profiling
# ---------------------------------------------------------------------------

def test_superstep_counters_per_variant(graph):
    """Profiled runs report iterations / halt / message volume for
    every superstep strategy; the frontier adds per-round occupancy.
    Profiling never changes the answer."""
    eng = LocalEngine(graph)
    defn = R.get("bfs")
    ref = np.asarray(eng.run(defn, {"sources": (0,)},
                             variant="dense").value)
    for variant in ("dense", "fused", "frontier"):
        r = eng.run(defn, {"sources": (0,)}, variant=variant,
                    profile=True)
        ss = r.meta["superstep"]
        assert ss["variant"] == variant
        assert ss["iterations"] >= 1
        assert ss["halted"] == (ss["iterations"] < ss["max_iters"])
        assert ss["message_bytes"] > 0
        assert np.asarray(r.value).tobytes() == ref.tobytes()
        if variant == "frontier":
            occ = ss["frontier_occupancy"]
            assert len(occ) == ss["iterations"]
            assert all(c >= 0 for c in occ)
        # profiling is opt-in: the unprofiled run carries no counters
        bare = eng.run(defn, {"sources": (0,)}, variant=variant)
        assert "superstep" not in bare.meta


def test_mixed_tier_drain_every_ticket_explained(graph):
    """The acceptance workload: a drained mixed-tier mix where every
    ticket's explain() shows candidates, queue wait, and (for executed
    tickets) the superstep counters."""
    qs = [GraphQuery.bfs([0], count_only=True),     # interactive
          GraphQuery.bfs([1]), GraphQuery.bfs([2]),  # fused batch
          GraphQuery.pagerank(max_iters=5)]          # fixpoint batch
    probe = _traced_service(graph)
    ests = sorted(P.plan_cost(probe.context("g").plan(q)) for q in qs)
    # split the tiers between the cheapest and the rest
    svc = _traced_service(
        graph, interactive_threshold_s=(ests[0] + ests[1]) / 2)
    ts = [svc.submit("g", q) for q in qs]
    assert {t.tier for t in ts} == {"interactive", "batch"}
    svc.drain()
    for t in ts:
        tr = svc.tracer.trace(t.ticket_id)
        assert tr.root.attrs["status"] == "done"
        assert tr.find("plan").attrs["candidates"]
        assert tr.find("queue-wait").attrs["wait_s"] >= 0
        text = svc.explain(t)
        assert "candidates (pool/engine/variant/mode):" in text
        assert "wait_s=" in text
    # pregel-backed tickets carry superstep counters on their execute
    for t in ts[1:3]:
        ex = svc.tracer.trace(t.ticket_id).find("execute")
        assert ex.attrs["superstep"]["iterations"] >= 1


# ---------------------------------------------------------------------------
# Tracing must not perturb anything
# ---------------------------------------------------------------------------

def test_tracing_is_invisible_in_results(graph):
    """Byte-identical values, identical iteration counts, identical
    scheduling counters — traced vs untraced."""
    def run(trace_depth):
        svc = GraphAnalyticsService(interactive_threshold_s=0.0,
                                    trace_depth=trace_depth)
        svc.add_graph("g", graph)
        qs = [GraphQuery.bfs([s]) for s in (0, 5, 9)] \
            + [GraphQuery.pagerank(max_iters=4),
               GraphQuery.degree_stats()]
        ts = [svc.submit("g", q) for q in qs]
        svc.drain(workers=2)
        rs = [svc.result(t) for t in ts]
        counters = svc.metrics()["counters"]
        return ([_bits(r.value) for r in rs],
                [r.iterations for r in rs], counters)
    off_bits, off_iters, off_counters = run(0)
    on_bits, on_iters, on_counters = run(64)
    assert on_bits == off_bits
    assert on_iters == off_iters
    assert on_counters == off_counters


def test_trace_ring_is_bounded(graph):
    svc = _traced_service(graph, trace_depth=2,
                          interactive_threshold_s=0.0, cache_size=0)
    ts = [svc.submit("g", GraphQuery.bfs([s])) for s in (0, 1, 2, 3)]
    svc.drain()
    counters = svc.tracer.counters_snapshot()
    assert counters["retained"] == 2
    assert counters["evicted"] == 2
    assert counters["tickets"] == 4
    assert svc.tracer.trace(ts[0].ticket_id) is None
    with pytest.raises(KeyError, match="aged out"):
        svc.explain(ts[0])
    svc.explain(ts[-1])                      # newest still retained
    with pytest.raises(ValueError, match="trace_depth"):
        obs.Tracer(trace_depth=0)


def test_explain_requires_tracing(graph):
    svc = GraphAnalyticsService()
    svc.add_graph("g", graph)
    t = svc.submit("g", GraphQuery.bfs([0]))
    svc.drain()
    assert svc.metrics()["trace"]["enabled"] == 0
    with pytest.raises(RuntimeError, match="tracing is off"):
        svc.explain(t)


def test_observer_seam_records_fault_and_transfer_events(graph,
                                                         flaky_algorithm):
    """Registry fault injections and ledger transfers reach the tracer
    through the observer seam; with no observers, emit() is a no-op."""
    obs.emit("fault", algorithm="nobody-listens")   # must not blow up
    pools = PL.PoolSet([PL.DevicePool("onprem"),
                        PL.DevicePool("cloud", compute_scale=1e-9)])
    svc = GraphAnalyticsService(
        pools=pools, interactive_threshold_s=0.0, trace_depth=8,
        retry=RetryPolicy(max_attempts=2, base_s=1e-4, cap_s=1e-3))
    # resident only on onprem: the compute-favoured cloud pool must
    # pull the snapshot across the link, charging a transfer
    svc.add_graph("g", graph, pools=["onprem"])
    R.install_fault(FLAKY, R.FailNTimes(1))
    t = svc.submit("g", GraphQuery.of(FLAKY))
    assert t.pool == "cloud"
    svc.drain()
    assert t.status == "done"
    faults = [(kind, attrs) for (_, kind, attrs) in svc.tracer.events
              if kind == "fault"]
    assert any(a["error"] is not None for _, a in faults)   # the injection
    assert any(a["error"] is None for _, a in faults)       # the success
    assert all(a["algorithm"] == FLAKY for _, a in faults)
    transfers = [attrs for (_, kind, attrs) in svc.tracer.events
                 if kind == "transfer"]
    assert transfers and all(a["bytes"] > 0 for a in transfers)
    # the executed ticket also carries the transfer as a span event
    tr = svc.tracer.trace(t.ticket_id)
    assert any(name == "transfer" for (_, name, _) in tr.root.events)


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------

def test_chrome_trace_export_and_schema(graph, tmp_path):
    svc = _traced_service(graph, interactive_threshold_s=0.0)
    ts = [svc.submit("g", GraphQuery.bfs([s])) for s in (0, 5)]
    svc.drain()
    path = tmp_path / "trace.json"
    doc = svc.tracer.export_chrome_trace(str(path))
    n = obs.validate_chrome_trace(str(path))       # re-parse from disk
    assert n == len(doc["traceEvents"]) > 0
    by_tid = {}
    for ev in doc["traceEvents"]:
        by_tid.setdefault(ev["tid"], []).append(ev)
    assert set(by_tid) == {t.ticket_id for t in ts}
    # the fused execute span appears once per member row, same span_id
    exec_ids = {tid: [e["args"]["span_id"] for e in evs
                      if e["name"] == "execute"]
                for tid, evs in by_tid.items()}
    assert all(len(ids) == 1 for ids in exec_ids.values())
    assert len({ids[0] for ids in exec_ids.values()}) == 1


@pytest.mark.parametrize("bad,match", [
    ('{"no": []}', "traceEvents"),
    ('{"traceEvents": [{"ph": "X"}]}', "missing"),
    ('{"traceEvents": [{"name": "x", "ph": "Q", "ts": 0, '
     '"pid": 1, "tid": 1}]}', "phase"),
    ('{"traceEvents": [{"name": "x", "ph": "X", "ts": 0, '
     '"pid": 1, "tid": 1}]}', "dur"),
], ids=["top-level", "fields", "phase", "dur"])
def test_chrome_trace_validator_rejects(bad, match):
    with pytest.raises(ValueError, match=match):
        obs.validate_chrome_trace(bad)


# ---------------------------------------------------------------------------
# Metrics exposition
# ---------------------------------------------------------------------------

def test_metrics_text_roundtrips_metrics(graph):
    """Every numeric leaf of metrics() appears in the exposition and
    parses back to the same value (None <-> NaN)."""
    svc = _traced_service(graph, interactive_threshold_s=0.0)
    for s in (0, 5):
        svc.submit("g", GraphQuery.bfs([s]))
    svc.drain()
    parsed = obs.parse_prometheus(svc.metrics_text())
    leaves: list = []
    obs._flatten(svc.metrics(), (), leaves)
    checked = 0
    for path, value in leaves:
        name = obs._metric_name("gas", path)
        if value is None:
            assert math.isnan(parsed[name]), name
        elif isinstance(value, (bool, int, float)):
            assert parsed[name] == pytest.approx(float(value)), name
        else:
            continue                          # strings ride as comments
        checked += 1
    assert checked >= 50                      # the surface is wide
    assert parsed["gas_trace_enabled"] == 1
    assert parsed["gas_accuracy_samples"] >= 1
    assert parsed["gas_counters_executed"] >= 1


def test_metric_names_keep_exponent_signs_apart():
    """Histogram bucket keys differ only in their exponent's sign: their
    metric names must differ too."""
    small = obs._metric_name("gas", ("latency", "batch", "le_1e-02"))
    large = obs._metric_name("gas", ("latency", "batch", "le_1e+02"))
    assert (small, large) == ("gas_latency_batch_le_1em02",
                              "gas_latency_batch_le_1ep02")
    assert obs._metric_name("gas", ("pools", "pool-a", "x.y")) == \
        "gas_pools_pool_a_x_y"


def test_latency_window_exact_flag():
    h = LatencyHistogram(max_samples=4)
    for x in (0.1, 0.2, 0.3):
        h.observe(x)
    snap = h.snapshot()
    assert snap["window_exact"] is True       # whole history retained
    assert snap["window_size"] == 3
    for x in (0.4, 0.5):
        h.observe(x)
    snap = h.snapshot()
    assert snap["window_exact"] is False      # oldest samples aged out
    assert snap["window_size"] == 4
    assert snap["count"] == 5                 # buckets keep everything
    assert snap["buckets"]["le_inf"] == 5
    assert snap["p50_s"] in (0.3, 0.4)        # window-local quantile


# ---------------------------------------------------------------------------
# Plan accuracy -> calibration feedback
# ---------------------------------------------------------------------------

def test_accuracy_meter_records_per_key(graph):
    svc = _traced_service(graph, interactive_threshold_s=0.0,
                          cache_size=0)
    for s in (0, 1):
        svc.submit("g", GraphQuery.bfs([s]))
    svc.drain()
    svc.call("g", GraphQuery.pagerank(max_iters=4))
    acc = svc.metrics()["accuracy"]
    assert acc["samples"] >= 2
    assert acc["mean_abs_rel_err"] is not None
    assert any(k.startswith("bfs|") for k in acc["by_key"])
    assert any(k.startswith("pagerank|") for k in acc["by_key"])
    for row in acc["by_key"].values():
        assert row["n"] >= 1
        assert row["est_s_mean"] > 0 and row["wall_s_mean"] > 0
        assert row["wall_over_est"] > 0


def test_fused_group_records_one_accuracy_sample(graph):
    svc = _traced_service(graph, interactive_threshold_s=0.0)
    for s in (0, 5, 9):
        svc.submit("g", GraphQuery.bfs([s]))
    svc.drain()
    acc = svc._accuracy
    samples = [s for key, dq in acc._samples.items()
               if key[0] == "bfs" for s in dq]
    assert len(samples) == 1                  # one fused run, one sample
    (est, wall, mode, width) = samples[0]
    assert width == 3 and est > 0 and wall > 0


def test_calibration_refit_from_production_traces(graph, tmp_path):
    """The loop closes: PlanAccuracyMeter samples feed
    emit_calibration directly, yielding a profile whose per-algorithm
    scale is the measured/modeled ratio from live traffic."""
    from benchmarks.algo_suite import emit_calibration
    svc = _traced_service(graph, interactive_threshold_s=0.0,
                          cache_size=0)
    for s in range(4):
        svc.submit("g", GraphQuery.bfs([s]))
    svc.drain()
    samples = svc._accuracy.calibration_samples()
    assert "bfs" in samples and samples["bfs"]
    for wall, est in samples["bfs"]:
        assert wall > 0 and est > 0
    profile = emit_calibration(str(tmp_path / "calib.json"), samples,
                               out=lambda *a, **k: None)
    ratios = sorted(w / e for w, e in samples["bfs"])
    assert profile.algo_time_scale["bfs"] == pytest.approx(
        float(np.median(ratios)))


def test_accuracy_meter_bounds_and_shape():
    m = obs.PlanAccuracyMeter(max_samples=3)
    for i in range(5):
        m.record("bfs", "local", "dense", None,
                 est_s=1.0, wall_s=2.0 + i)
    snap = m.snapshot()
    assert snap["samples"] == 3               # rolling window
    row = snap["by_key"]["bfs|local|dense|-"]
    assert row["n"] == 3
    assert row["wall_over_est"] == pytest.approx(5.0)  # mean of 4,5,6
    assert snap["mean_abs_rel_err"] == pytest.approx(4.0)
    assert m.calibration_samples() == {"bfs": [(4.0, 1.0), (5.0, 1.0),
                                               (6.0, 1.0)]}


def test_infeasible_candidates_carry_the_reason():
    """At paper scale the local engine exceeds its memory budget: its
    candidate row survives in the table, marked infeasible with the
    reason, while distributed is chosen."""
    g = P.GraphStats(n_vertices=2_410_000_000, n_edges=1_500_000_000,
                     bytes_coo=1_500_000_000 * 12)
    q = P.spec_for("connected_components", g)
    plan = P.choose_engine(g, q, 256)
    assert plan.engine == "distributed"
    assert plan.candidates
    assert sum(c.chosen for c in plan.candidates) == 1
    local = next(c for c in plan.candidates if c.engine == "local")
    assert not local.feasible
    assert not math.isfinite(local.est_s)
    assert local.note == "exceeds local memory budget"


# ---------------------------------------------------------------------------
# The superstep's phases and the service's host work on the profiler trace
# ---------------------------------------------------------------------------

V_SCOPES = 64


def _scoped_ops(fn, *args, kinds=("gather", "scatter")) -> list:
    """``(kind, innermost pregel.* scope or None)`` of each instruction
    of a kind in ``kinds`` in the compiled program's HLO."""
    out = []
    for line in jax.jit(fn).lower(*args).compile().as_text().splitlines():
        m = re.search(r"= \S+ (\w+)\(", line)
        op = re.search(r'op_name="([^"]*)"', line)
        if m and m.group(1) in kinds and op:
            scopes = [p for p in op.group(1).split("/")
                      if p.startswith("pregel.")]
            out.append((m.group(1), scopes[-1] if scopes else None))
    return out


def _scopes_graph():
    rng = np.random.default_rng(5)
    src = rng.integers(0, V_SCOPES, 4 * V_SCOPES)
    dst = rng.integers(0, V_SCOPES, 4 * V_SCOPES)
    return G.build_coo(src, dst, V_SCOPES, symmetrize=True)


_SUM_SPEC = pregel.PregelSpec(
    message=lambda x, w: x * w, combine="sum",
    apply=lambda s, agg, ids, gv: 0.5 * s + agg, identity=0.0,
    elementwise_message=True)


@pytest.mark.parametrize("combine", ["sum", "min"])
def test_dense_superstep_ops_carry_their_phase(combine):
    """The gather, the segment scatter and (for min/max) the empty-count
    scatter of the dense program carry their phase's scope in their HLO
    ``op_name``."""
    spec = _SUM_SPEC if combine == "sum" else traversal._SSSP_SPEC
    # edge shards without run offsets: the scatter path
    sg = dataclasses.replace(partition_1d(_scopes_graph(), 1),
                             in_offsets=None)
    init = jnp.ones((V_SCOPES,), jnp.float32)

    def program(src, dst, w, state):
        edges = dataclasses.replace(sg, src=src, dst=dst, w=w)
        return pregel.run_pregel(spec, edges, state, 4)

    ops = _scoped_ops(program, sg.src, sg.dst, sg.w, init)
    assert ("gather", "pregel.gather") in ops
    scatters = sorted(s for k, s in ops if k == "scatter")
    if combine == "sum":
        assert scatters == ["pregel.combine"]
    else:
        assert scatters == ["pregel.combine", "pregel.combine_empty"]


@pytest.mark.parametrize("combine", ["sum", "min"])
def test_runs_superstep_ops_carry_their_phase(combine, monkeypatch):
    """With run offsets the dense program's kernel path (interpreted
    here; off a TPU it would lower to the scatter) scatters nothing: its
    gathers are the source-state gather (``pregel.gather``) and the read
    of each run's end (``pregel.combine``), and min/max find empty
    vertices by a compare under ``pregel.combine_empty``."""
    monkeypatch.setattr(pregel, "segment_runs", functools.partial(
        segment_runs_pallas, interpret=True))
    monkeypatch.setattr(pregel, "_JIT_CACHE", collections.OrderedDict())
    spec = _SUM_SPEC if combine == "sum" else traversal._SSSP_SPEC
    sg = partition_1d(_scopes_graph(), 1)
    assert sg.in_offsets is not None
    init = jnp.ones((V_SCOPES,), jnp.float32)

    def program(src, dst, w, state):
        edges = dataclasses.replace(sg, src=src, dst=dst, w=w)
        return pregel.run_pregel(spec, edges, state, 4)

    ops = _scoped_ops(program, sg.src, sg.dst, sg.w, init,
                      kinds=("gather", "scatter", "compare", "select"))
    assert not [s for k, s in ops if k == "scatter"]
    gathers = {s for k, s in ops if k == "gather"}
    assert {"pregel.gather", "pregel.combine"} <= gathers
    empty = any(s == "pregel.combine_empty" for _, s in ops)
    assert empty == (combine == "min")


class _Events:
    def __init__(self):
        self.events = []

    def record_event(self, kind, attrs):
        self.events.append((kind, attrs))

    def combines(self):
        return [a["combine"] for k, a in self.events
                if k == "pregel.dispatch"]


def test_dispatch_events_name_the_combine():
    """``pregel.dispatch`` says which combine ran: ``runs`` for a
    LocalEngine PageRank job (its shards carry run offsets),
    ``scatter`` for a hand-built ShardedCOO without them and on the
    mesh path."""
    from repro.launch.mesh import make_mesh
    g = _scopes_graph()
    init = jnp.ones((V_SCOPES,), jnp.float32)
    rec = _Events()
    obs.install_observer(rec)
    try:
        LocalEngine(g).run("pagerank", {"max_iters": 2})
        runs = rec.combines()
        sg = partition_1d(g, 1)
        hand = pregel.ShardedCOO(sg.src, sg.dst, sg.w, sg.n_vertices,
                                 sg.n_edges, sg.n_data, sg.n_model,
                                 sg.e_shard, sg.v_local)
        pregel.run_pregel(_SUM_SPEC, hand, init, 2)
        mesh = make_mesh((1,), ("data",))
        pregel.run_pregel(_SUM_SPEC, partition_1d(g, 1, mesh=mesh), init,
                          2, mesh=mesh)
        # offsets on the shards do not take the mesh path off the scatter
        pregel.run_pregel(_SUM_SPEC, sg, init, 2, mesh=mesh)
    finally:
        obs.uninstall_observer(rec)
    assert runs == ["runs"]
    assert rec.combines()[1:] == ["scatter"] * 3


@pytest.mark.parametrize("variant", ["fused", "frontier"])
def test_variant_superstep_ops_carry_their_phase(variant):
    """The fused variant's gather and reduce, and the frontier
    variant's row gathers and scatter, carry their phase's scope."""
    g = _scopes_graph()
    src = np.asarray(g.src)[: g.n_edges]
    dst = np.asarray(g.dst)[: g.n_edges]
    if variant == "fused":
        width = int(np.bincount(dst, minlength=V_SCOPES).max())
        ell = G.build_ell(src, dst, V_SCOPES, width, direction="in")
        run, kinds = pregel.run_pregel_fused, ("gather", "reduce")
    else:
        width = int(np.bincount(src, minlength=V_SCOPES).max())
        ell = G.build_ell(src, dst, V_SCOPES, width, direction="out")
        run, kinds = pregel.run_pregel_frontier, ("gather", "scatter")
    init = jnp.full((V_SCOPES,), jnp.inf).at[0].set(0.0)
    ops = _scoped_ops(lambda e, s: run(traversal._SSSP_SPEC, e, s, 4),
                      ell, init, kinds=kinds)
    assert ops and all(s == "pregel.gather" for k, s in ops
                       if k == "gather")
    assert (kinds[1], "pregel.combine") in ops


def _host_spans(log_dir) -> list:
    """``(name, start_ns, end_ns, stats)`` of the ``service.``/``pregel.``
    events on the host planes of the one trace under ``log_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return sorted(
        ((e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
         for plane in ProfileData.from_file(path).planes
         if plane.name.startswith("/host:")
         for line in plane.lines for e in line.events
         if e.name.startswith(("service.", "pregel."))),
        key=lambda e: e[1])


def test_service_and_dispatch_spans_on_the_profiler_trace(tmp_path):
    """Two PageRank jobs and two WCC jobs through a traced service under
    the profiler: each job's host work is on the timeline, its program
    call a ``pregel.dispatch`` inside ``service.execute``.  PageRank
    builds its program anew every job (two misses), WCC reuses its
    (miss, then hit).  The tracer's dispatch events bracket the same
    work inside each ticket's execute span."""
    # a vertex count no other test uses, so WCC's first program is new
    n = 173
    src, dst = S.user_follow_graph(n, 4.0, seed=3)
    svc = GraphAnalyticsService(trace_depth=16, cache_size=0)
    svc.add_graph("g", G.build_coo(src, dst, n, symmetrize=True))
    queries = [GraphQuery.pagerank(max_iters=3)] * 2 \
        + [GraphQuery.connected_components()] * 2
    tickets = []
    with jax.profiler.trace(str(tmp_path)):
        for q in queries:
            tickets.append(svc.submit("g", q))
            svc.result(tickets[-1])
    spans = _host_spans(tmp_path)
    names = [s[0] for s in spans]
    assert names.count("service.submit") == names.count("service.plan") \
        == names.count("service.execute") == 4
    assert names.count("service.resolve") == 4
    executes = [s for s in spans if s[0] == "service.execute"]
    dispatches = [s for s in spans if s[0] == "pregel.dispatch"]
    assert [d[3].get("jit_cache") for d in dispatches] == [
        "miss", "miss", "miss", "hit"]
    for (_, a, b, _), (_, c, d, _) in zip(executes, dispatches):
        assert a <= c <= d <= b
    for submit in (s for s in spans if s[0] == "service.submit"):
        assert any(p[0] == "service.plan" and submit[1] <= p[1]
                   and p[2] <= submit[2] for p in spans)
    events = [a for _, kind, a in svc.tracer.events
              if kind == "pregel.dispatch"]
    assert [a["jit_cache"] for a in events] == ["miss", "miss", "miss",
                                                "hit"]
    for t, a in zip(tickets, events):
        execute = svc.tracer.trace(t.ticket_id).find("execute")
        assert execute.t0 <= a["t0"] <= a["t1"] <= execute.t1
