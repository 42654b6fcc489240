"""Fig. 5 reproduction: LocalEngine (Neo4j analogue) vs DistributedEngine
(Spark analogue) on combined connected users, sweeping graph scale and
output cardinality.

The paper's findings this must reproduce qualitatively:
  1. small/medium graphs: the local engine wins;
  2. the gap narrows as scale grows (the BSP engine's fixed per-superstep
     cost amortizes; on real multi-chip meshes it then *wins* — here both
     run on one CPU device so we report the trend + the planner's
     projected crossover for the production mesh);
  3. count-only output is dramatically cheaper than full-table output on
     the local engine ('<2 s vs ~10 min' in the paper).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import time_fn, csv_row
from repro.core import graph as G
from repro.core import planner as P
from repro.core.engines import LocalEngine, DistributedEngine
from repro.data import synthetic as S
from repro.utils.compile_cache import enable_compile_cache


def run(out=print):
    rows = []
    for n_vertices in [2_000, 20_000, 100_000]:
        src, dst = S.user_follow_graph(n_vertices, 4.0, seed=1)
        g = G.build_coo(src, dst, n_vertices, symmetrize=True)

        local = LocalEngine(g)
        t_local, r_local = time_fn(
            lambda: local.connected_components().value)
        dist = DistributedEngine(g, n_data=4)
        t_dist, r_dist = time_fn(
            lambda: dist.connected_components().value)
        assert (np.asarray(r_local) == np.asarray(r_dist)).all()

        # count-only on the local engine (the paper's 2s-vs-10min query)
        t_count, _ = time_fn(lambda: local.num_components().value)

        # host materialization of the full table (the output cost the
        # planner charges for table-returning queries)
        t_table, _ = time_fn(
            lambda: np.asarray(local.connected_components().value))

        stats = P.GraphStats.of(g)
        plan = P.choose_engine(
            stats, P.spec_for("connected_components", stats), 256)
        rows.append((n_vertices, t_local, t_dist, t_count, t_table,
                     plan.engine))
        out(csv_row(f"fig5/cc_local_v{n_vertices}", t_local,
                    f"ncomp_table"))
        out(csv_row(f"fig5/cc_bsp_v{n_vertices}", t_dist,
                    f"ratio={t_dist/t_local:.2f}x"))
        out(csv_row(f"fig5/cc_count_v{n_vertices}", t_count,
                    f"count_vs_table={t_table/max(t_count,1e-9):.2f}x"))

    # planner projection across the full Fig. 5 range
    flips = []
    for v in [10**4, 10**5, 10**6, 10**7, 10**8, 10**9]:
        stats = P.GraphStats(v, v * 5, v * 5 * 12)
        plan = P.choose_engine(
            stats, P.spec_for("connected_components", stats), 256)
        flips.append((v, plan.engine))
    cross = next((v for v, e in flips if e == "distributed"), None)
    out(csv_row("fig5/planner_crossover_vertices", 0.0,
                f"crossover_at_V={cross}"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
