"""Incremental-vs-cold sweep: what the daily delta actually buys.

The paper's pipeline re-lands the whole follow graph every day even
though consecutive snapshots differ by a small edge delta.  This sweep
measures the end-to-end payoff of the time-versioned catalog: register
a base snapshot, answer a query cold, land a delta snapshot
(``add_snapshot(..., added=...)``), and answer the *same* query on the
new version through the service — which seeds a localized incremental
repair (CC/BFS/k-core, byte-identical to cold) or a warm-started
fixpoint (PageRank/HITS, same vector within tolerance) from the
parent's cached result.

Axes: delta fraction (0.1% .. 10% of the edge set) x graph size.  Per
cell we record the cold wall (the same engine running the query with
no seed), the incremental wall (the same context executing the seeded
plan), the speedup, and the iterations cold vs seeded.  **Parity is
asserted here**, not just in the test suite: exact algorithms must
match the cold run byte for byte, fixpoints within their convergence
tolerance.

The graphs are degree-capped (the paper's MaxAdjacentNodes knob,
Table I): the production pipeline bounds adjacency skew before
shipping the graph, and the bounded ELL width is what lets the
frontier superstep run the repair wavefront in work proportional to
the *actual* frontier instead of the whole edge set.

Both paths are warmed before timing (derived graph state built, XLA
programs compiled), so the walls compare pure execution — the
recurring per-query cost the daily cadence actually pays.  The cold
wall is the *best* of the planner-chosen variant and the dense oracle,
so the reported speedup is conservative.  Results land in
``BENCH_incremental.json`` (``--out`` overrides).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import graph as G
from repro.core import planner as P
from repro.core import registry as R
from repro.core.query import GraphQuery
from repro.core.service import GraphAnalyticsService
from repro.data import synthetic as S
from repro.utils.compile_cache import enable_compile_cache

SIZES = (50_000, 200_000)
DELTA_FRACTIONS = (0.001, 0.01, 0.1)
#: exact algorithms: seeded repair must be byte-identical to cold
EXACT = ("connected_components", "bfs")
#: fixpoint algorithms: seeded run must land within tol, fewer iters
FIXPOINT = ("pagerank", "hits")
#: the paper's follow graph averages ~30 edges per vertex (30 B edges
#: over ~1 B vertices); 16 keeps the sweep in that density regime
#: without blowing the CI wall clock
MEAN_DEGREE = 16.0
#: MaxAdjacentNodes: per-endpoint adjacency cap applied before the
#: symmetrize, the paper's Table I skew bound
DEGREE_CAP = 64


def _queries(coo: G.GraphCOO) -> dict:
    # BFS from the best-connected vertex: the degree cap can orphan a
    # low-degree id whose few followees were all over-subscribed hubs
    deg = np.bincount(np.asarray(coo.src)[: coo.n_edges],
                      minlength=coo.n_vertices)
    return {
        "connected_components": GraphQuery.of("connected_components"),
        "bfs": GraphQuery.of("bfs", sources=(int(np.argmax(deg)),)),
        "pagerank": GraphQuery.of("pagerank", max_iters=100),
        "hits": GraphQuery.of("hits", max_iters=50),
    }


def _group_rank(keys: np.ndarray) -> np.ndarray:
    """Occurrence rank of each element within its value group."""
    order = np.argsort(keys, kind="stable")
    sorted_keys = keys[order]
    starts = np.r_[0, np.flatnonzero(np.diff(sorted_keys)) + 1]
    lengths = np.diff(np.r_[starts, len(keys)])
    rank = np.empty(len(keys), np.int64)
    rank[order] = (np.arange(len(keys))
                   - np.repeat(starts, lengths))
    return rank


def _base_graph(n: int, seed: int = 0) -> G.GraphCOO:
    src, dst = S.user_follow_graph(n, mean_degree=MEAN_DEGREE, seed=seed)
    # MaxAdjacentNodes: keep each vertex's first DEGREE_CAP edges per
    # endpoint role, bounding the post-symmetrize degree at 2*cap
    keep = ((_group_rank(src) < DEGREE_CAP)
            & (_group_rank(dst) < DEGREE_CAP))
    # symmetrized: CC requires it, and the traversal/fixpoint answers
    # are just as meaningful on the undirected follow graph
    return G.build_coo(src[keep], dst[keep], n, symmetrize=True)


def _delta_edges(n_vertices: int, n_edges: int, rng) -> np.ndarray:
    return np.stack([rng.integers(0, n_vertices, n_edges),
                     rng.integers(0, n_vertices, n_edges)], axis=1)


def _materialize(value):
    """Force device results to the host so timings include them."""
    if isinstance(value, dict):
        for v in value.values():
            np.asarray(v)
    else:
        np.asarray(value)


def _wall(fn, iters: int = 3):
    """Median wall seconds over ``iters`` runs (callers warm first)."""
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        r = fn()
        _materialize(r.value)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), r


def _assert_parity(alg: str, seeded, cold, tol: float = 1e-4) -> None:
    if alg in EXACT:
        a, b = np.asarray(seeded), np.asarray(cold)
        if not np.array_equal(a, b):
            raise AssertionError(
                f"{alg}: seeded result differs from cold recompute "
                f"({int(np.sum(a != b))} mismatching entries)")
        return
    if alg == "hits":
        for half in ("hubs", "authorities"):
            if not np.allclose(np.asarray(seeded[half]),
                               np.asarray(cold[half]), atol=tol):
                raise AssertionError(f"hits: {half} outside tol {tol}")
        return
    if not np.allclose(np.asarray(seeded), np.asarray(cold), atol=tol):
        raise AssertionError(f"{alg}: seeded vector outside tol {tol}")


def _run_cell(coo: G.GraphCOO, added: np.ndarray, alg: str, q) -> dict:
    """One (graph, delta, algorithm) measurement through a fresh
    service: land the base snapshot, answer ``q`` cold (populating the
    seed), land the delta version, then time the cold and the seeded
    execution on the *same* child context with derived state and
    compilation already paid on both paths."""
    svc = GraphAnalyticsService()
    svc.add_snapshot("g", coo, as_of=0)
    parent = svc.call("g", q)               # the seed-to-be
    svc.add_snapshot("g", as_of=1, added=added)
    ctx = svc.context("g", as_of=1)

    # cold: same engine, same child bytes, no seed.  Timed under both
    # the planner-chosen variant and the dense oracle; the *faster* one
    # is the baseline, so the speedup is conservative.
    plan_cold = ctx.plan(q)
    engine = ctx.engine(plan_cold.engine)

    def cold_variant_fn(variant):
        def fn():
            return engine.run(q.algorithm, q.params,
                              count_only=q.count_only, variant=variant)
        return fn

    cold_fn = cold_variant_fn(plan_cold.variant)
    cold_dense_fn = (cold_variant_fn("dense")
                     if "dense" in (R.get(alg).variants or ()) else cold_fn)

    # seeded: the catalog's lineage lookup + seeded plan, executed
    # through the context (svc.call would answer repeats from the
    # result cache, which is exactly what a timing loop must not hit)
    seed, seed_mode = svc._seed_for(ctx, q)
    plan_inc = ctx.plan(q, seed_mode=seed_mode)

    def inc_fn():
        return ctx.execute(q, plan_inc, seed=seed)

    cold_fn()                   # build derived state + compile, all paths
    cold_dense_fn()
    inc_fn()
    t_cold, cold = _wall(cold_fn)
    t_dense, _ = _wall(cold_dense_fn)
    t_cold = min(t_cold, t_dense)
    t_inc, seeded = _wall(inc_fn)

    _assert_parity(alg, seeded.value, cold.value)
    # the real service path once more, for the meter + mode bookkeeping
    served = svc.call("g", q, as_of=1)
    assert served.meta.get("mode") == seeded.meta.get("mode")
    metr = svc.metrics()["incremental"]
    return {
        "algorithm": alg,
        "mode": seeded.meta.get("mode") or "full",
        "cold_s": t_cold,
        "incremental_s": t_inc,
        "speedup": t_cold / max(t_inc, 1e-9),
        "iters_cold": cold.iterations,
        "iters_seeded": seeded.iterations,
        "iterations_saved": metr["iterations_saved"],
        "delta_bytes_applied": metr["delta_bytes_applied"],
        "parent_iters": parent.iterations,
    }


def sweep(sizes=SIZES, fractions=DELTA_FRACTIONS, seed: int = 0) -> dict:
    P.set_calibration(None)       # analytic model: box-independent plans
    rng = np.random.default_rng(seed)
    rows = []
    for n in sizes:
        coo = _base_graph(n, seed=seed)
        queries = _queries(coo)
        for frac in fractions:
            n_add = max(1, int(frac * coo.n_edges))
            added = _delta_edges(n, n_add, rng)
            for alg, q in queries.items():
                # HITS' doubled role graph is heavy at the top size;
                # its iteration accounting is fully covered at the
                # smaller scales
                if alg == "hits" and n > 100_000:
                    continue
                row = _run_cell(coo, added, alg, q)
                row.update(n_vertices=n, n_edges=coo.n_edges,
                           delta_fraction=frac, n_added=n_add)
                rows.append(row)
                print(f"V={n:>7} frac={frac:<6} {alg:<22} "
                      f"mode={row['mode']:<11} "
                      f"cold={row['cold_s']*1e3:8.1f}ms "
                      f"inc={row['incremental_s']*1e3:8.1f}ms "
                      f"speedup={row['speedup']:6.1f}x "
                      f"iters {row['iters_cold']}->{row['iters_seeded']}")
    # headline: best exact-algorithm speedup at <=1% delta (the
    # acceptance bar: incremental repair of a small daily delta)
    small = [r for r in rows if r["algorithm"] in EXACT
             and r["delta_fraction"] <= 0.01 and r["mode"] == "incremental"]
    warm = [r for r in rows if r["algorithm"] in FIXPOINT
            and r["mode"] == "warm"]
    return {
        "sizes": list(sizes),
        "delta_fractions": list(fractions),
        "rows": rows,
        "exact_small_delta_max_speedup": max(
            (r["speedup"] for r in small), default=None),
        "exact_small_delta_min_speedup": min(
            (r["speedup"] for r in small), default=None),
        "warm_iterations_saved_total": sum(
            max(r["parent_iters"] - (r["iters_seeded"] or 0), 0)
            for r in warm),
        "parity": "asserted per cell (byte-identical for exact, "
                  "tolerance for fixpoints)",
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_incremental.json")
    ap.add_argument("--quick", action="store_true",
                    help="small single-size sweep (CI smoke)")
    args = ap.parse_args(argv)
    if args.quick:
        result = sweep(sizes=(20_000,), fractions=(0.001, 0.01))
    else:
        result = sweep()
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"wrote {args.out}: exact<=1% speedup "
          f"{result['exact_small_delta_min_speedup']:.1f}x .. "
          f"{result['exact_small_delta_max_speedup']:.1f}x, "
          f"warm iterations saved "
          f"{result['warm_iterations_saved_total']}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
