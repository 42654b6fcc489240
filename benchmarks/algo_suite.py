"""Algorithm-suite sweep: per-workload local-vs-distributed crossover in
the Fig. 5 style, across the full vertex-program library.

The suite is *registry-driven*: it iterates every ``AlgorithmDef`` with
an ``example_params`` entry, so a newly registered algorithm shows up in
the sweep (and in the local==distributed parity assertion) without any
edit here.  For every algorithm this measures, at each graph scale:

  * LocalEngine wall time (the Neo4j-analogue interactive path);
  * DistributedEngine wall time (edge-partitioned BSP, n_data=4 — on a
    one-device box this exposes the partitioning/launch overhead whose
    amortization is exactly the Fig. 5 story);
  * the count-only fast-path time where the algorithm has one (the
    paper's '<2 s count vs ~10 min table' pattern);
  * every registered execution *variant* where an algorithm has several
    (triangle counting's bitset vs ELL-intersect paths) — timed
    separately, asserted equal, with the planner's projected
    variant-selection crossover reported alongside;
  * the planner's projected crossover scale for a 256-chip mesh — each
    algorithm crosses at a different V because its iteration count,
    state bytes and message volume differ (triangle counting's bitset
    state crosses earliest, degree-like scans latest).

Results double as calibration input for the planner constants:
``--emit-calibration profile.json`` fits one measured/modeled wall-clock
ratio per algorithm from the sweep and writes a
``planner.CalibrationProfile`` that ``planner.load_calibration`` applies
process-wide — including the service tier thresholds, which are derived
from the measured interactive (count-path) latencies.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from benchmarks.common import time_fn, csv_row
from repro.core import graph as G
from repro.core import planner as P
from repro.core import registry as R
from repro.core.engines import LocalEngine, DistributedEngine
from repro.data import synthetic as S
from repro.utils.compile_cache import enable_compile_cache


def _build(n_vertices: int, symmetric: bool) -> G.GraphCOO:
    src, dst = S.user_follow_graph(n_vertices, 4.0, seed=1)
    keep = src != dst
    return G.build_coo(src[keep], dst[keep], n_vertices,
                       symmetrize=symmetric)


def _suite():
    """Registered algorithms that declared representative parameters."""
    return [(name, defn) for name, defn in R.items()
            if defn.example_params is not None]


def _assert_same(name: str, a, b) -> None:
    if isinstance(a, dict):
        assert sorted(a) == sorted(b), name
        for k in a:
            _assert_same(f"{name}[{k}]", a[k], b[k])
        return
    if isinstance(a, tuple):
        for x, y in zip(a, b):
            _assert_same(name, x, y)
        return
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape, name
    if np.issubdtype(a.dtype, np.floating):
        # summation order differs across edge shards
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7, err_msg=name)
    else:
        assert (a == b).all(), name


def run(out=print, samples=None):
    """The sweep.  ``samples``, when given, is filled with calibration
    inputs: per-algorithm ``[(measured_s, modeled_s), ...]`` pairs for
    the local engine, plus measured count-path latencies under the
    ``"_count_times"`` key (the tier-threshold input)."""
    rows = []
    for n_vertices in [2_000, 20_000]:
        graphs = {sym: _build(n_vertices, sym) for sym in (False, True)}
        locals_ = {sym: LocalEngine(g) for sym, g in graphs.items()}
        dists = {sym: DistributedEngine(g, n_data=4)
                 for sym, g in graphs.items()}
        for name, defn in _suite():
            sym = defn.requires_symmetric
            params = dict(defn.example_params)
            t_local, r_local = time_fn(
                lambda: locals_[sym].run(defn, params).value)
            out(csv_row(f"algo_suite/{name}_local_v{n_vertices}", t_local))
            if samples is not None:
                # measured-vs-modeled under the *analytic* defaults so a
                # previously loaded profile never skews a re-fit
                stats = P.GraphStats.of(graphs[sym])
                spec = P.best_spec_for_engine(
                    stats, P.specs_for(name, stats, **params), "local")
                modeled = P.estimate_local_cost(
                    stats, spec, profile=P.CalibrationProfile())
                if np.isfinite(modeled):
                    samples.setdefault(name, []).append((t_local, modeled))
            var_times = {}
            for var in sorted(defn.variants or ()):
                # each execution strategy timed on its own; the bitset
                # path at 20k V is exactly the pre-ELL-intersect wall
                t_var, r_var = time_fn(
                    lambda: locals_[sym].run(defn, params,
                                             variant=var).value)
                _assert_same(f"{name}:{var}", r_local, r_var)
                out(csv_row(f"algo_suite/{name}_{var}_v{n_vertices}",
                            t_var))
                var_times[var] = t_var
            if samples is not None and {"dense", "fused",
                                        "frontier"} <= set(var_times):
                # superstep strategies: measured wall ratios vs the
                # dense oracle calibrate the per-variant edge-bytes
                # factors (`planner.superstep_specs`)
                samples.setdefault("_superstep_times", []).append(
                    var_times)
            if "distributed" in defn.engines:
                t_dist, r_dist = time_fn(
                    lambda: dists[sym].run(defn, params).value)
                _assert_same(name, r_local, r_dist)
                out(csv_row(f"algo_suite/{name}_bsp_v{n_vertices}", t_dist,
                            f"bsp_ratio={t_dist / t_local:.2f}x"))
            if defn.has_count_path:
                t_count, _ = time_fn(
                    lambda: locals_[sym].run(defn, params,
                                             count_only=True).value)
                out(csv_row(
                    f"algo_suite/{name}_count_v{n_vertices}", t_count,
                    f"count_vs_table={t_local / max(t_count, 1e-9):.2f}x"))
                if samples is not None:
                    samples.setdefault("_count_times", []).append(t_count)
            rows.append((name, n_vertices, t_local))

    # planner-projected crossover per algorithm on the production mesh —
    # the per-workload Fig. 5 family
    for name, defn in R.items():
        if "distributed" not in defn.engines:
            continue
        cross = None
        for v in [10**4, 10**5, 10**6, 10**7, 10**8, 10**9, 10**10]:
            stats = P.GraphStats(v, v * 5, v * 5 * 12)
            plan = P.choose_engine(stats, P.spec_for(name, stats), 256)
            if plan.engine == "distributed":
                cross = v
                break
        out(csv_row(f"algo_suite/crossover_{name}", 0.0,
                    f"crossover_at_V={cross}"))

    # variant-selection crossovers: where the planner's cheapest
    # feasible strategy flips (bitset -> intersect for triangles), and
    # where the multi-variant plan finally leaves the local engine —
    # the headline being how far past the bitset wall intersect keeps
    # triangle queries local
    for name, defn in R.items():
        if not defn.variants:
            continue
        var_cross = eng_cross = None
        prev = None
        for v in [10**3, 10**4, 10**5, 10**6, 10**7, 10**8, 10**9]:
            stats = P.GraphStats(v, v * 5, v * 5 * 12)
            plan = P.choose_plan(stats, P.specs_for(name, stats), 256)
            if prev is not None and plan.variant != prev and not var_cross:
                var_cross = f"{prev}->{plan.variant}_at_V={v}"
            prev = plan.variant
            if plan.engine == "distributed" and eng_cross is None:
                eng_cross = v
        out(csv_row(f"algo_suite/variant_crossover_{name}", 0.0,
                    var_cross or "no_flip"))
        out(csv_row(f"algo_suite/variant_engine_crossover_{name}", 0.0,
                    f"local_until_V={eng_cross}"))
    return rows


def emit_calibration(path, samples, out=print) -> P.CalibrationProfile:
    """Fit a :class:`planner.CalibrationProfile` from sweep samples and
    write it to ``path``.

    Per algorithm, the measured per-algorithm constant is the median
    measured/modeled wall-clock ratio over the sweep's scales — the one
    multiplier that anchors that algorithm's analytic estimate to real
    executions on this box.  The interactive tier threshold is derived
    from the measured count-path latencies (the paper's interactive
    query class): generously above every observed one, so genuinely
    interactive shapes classify interactive while table-scale work
    stays batch.  Empty ``samples`` writes the analytic defaults — the
    profile round-trips regardless.
    """
    scales = {}
    for name, pairs in samples.items():
        if name.startswith("_") or not pairs:
            continue
        ratios = sorted(t / m for t, m in pairs if m > 0)
        scales[name] = float(np.median(ratios))
    kwargs = {}
    count_times = samples.get("_count_times") or []
    if count_times:
        kwargs["interactive_threshold_s"] = float(
            max(10.0 * max(count_times), 1e-3))
    superstep = samples.get("_superstep_times") or []
    if superstep:
        # per-variant edge-bytes factor anchored to the dense oracle:
        # factor_v = dense_factor * median(t_v / t_dense) across the
        # sweep — on a CPU host the frontier's scatter loop can fit
        # *above* 1.0, which is exactly the feedback that keeps the
        # planner from picking it where it does not pay off
        fitted = {"dense": P._SUPERSTEP_EDGE_BYTES["dense"]}
        for var in ("fused", "frontier"):
            ratios = sorted(vt[var] / vt["dense"] for vt in superstep
                            if vt["dense"] > 0)
            if ratios:
                fitted[var] = float(fitted["dense"]
                                    * np.median(ratios))
        kwargs["superstep_edge_bytes"] = fitted
    profile = P.CalibrationProfile(
        algo_time_scale=scales, source="benchmarks/algo_suite.py",
        platform=jax.default_backend(), **kwargs)
    profile.to_json(path)
    out(csv_row("algo_suite/calibration_written", 0.0,
                f"path={path} algorithms={len(scales)}"))
    return profile


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--emit-calibration", metavar="PATH", default=None,
                    help="write measured per-algorithm planner constants "
                         "to PATH (loadable via planner.load_calibration)")
    args = ap.parse_args(argv)
    samples: dict = {}
    run(samples=samples if args.emit_calibration else None)
    if args.emit_calibration:
        emit_calibration(args.emit_calibration, samples)


if __name__ == "__main__":
    enable_compile_cache()
    main()
