"""Graph-analytics job launcher — the platform CLI the paper's interface
layer would call.

    PYTHONPATH=src python -m repro.launch.run_graph \
        --job cc --vertices 20000 --count-only
    PYTHONPATH=src python -m repro.launch.run_graph \
        --job two-hop --vertices 5000 --engine distributed
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core import graph as G
from repro.core.query import GraphQuery, GraphPlatform
from repro.data import synthetic as S
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--job", choices=["pagerank", "cc", "two-hop", "stats"],
                    default="cc")
    ap.add_argument("--vertices", type=int, default=20_000)
    ap.add_argument("--mean-degree", type=float, default=5.0)
    ap.add_argument("--count-only", action="store_true")
    ap.add_argument("--engine", choices=["auto", "local", "distributed"],
                    default="auto")
    ap.add_argument("--n-data", type=int, default=1,
                    help="edge shards for the distributed engine")
    args = ap.parse_args()
    enable_compile_cache()

    n = args.vertices
    if args.job == "two-hop":
        u, i = S.safety_bipartite_graph(n, max(n // 4, 10), seed=0)
        coo = G.build_coo(u, i, int(max(u.max(), i.max())) + 1)
        query = GraphQuery.two_hop(n_users=n, count_only=args.count_only)
    else:
        src, dst = S.user_follow_graph(n, args.mean_degree, seed=0)
        sym = args.job == "cc"
        coo = G.build_coo(src, dst, n, symmetrize=sym)
        query = {"pagerank": GraphQuery.pagerank(),
                 "cc": GraphQuery.connected_components(
                     count_only=args.count_only),
                 "stats": GraphQuery.degree_stats()}[args.job]

    platform = GraphPlatform(
        coo, n_data=args.n_data,
        force_engine=None if args.engine == "auto" else args.engine)
    plan = platform.plan(query)
    print(f"[plan] engine={plan.engine} | {plan.reason}")
    t0 = time.time()
    r = platform.query(query)
    dt = time.time() - t0
    val = r.value
    if hasattr(val, "shape") and getattr(val, "size", 2) > 8:
        val = f"array{tuple(np.asarray(val).shape)}"
    print(f"[done] engine={r.engine} iters={r.iterations} "
          f"wall={dt:.3f}s result={val}")


if __name__ == "__main__":
    main()
