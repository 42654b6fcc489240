"""Fig. 7 reproduction: combined connected users running time —
unified-graph CC in one XLA program (ours/GraphFrames-equivalent) vs the
legacy per-edge-set CC + merge pipeline.  Paper reports ~37x."""
from __future__ import annotations

import numpy as np

from benchmarks.common import time_fn, time_host, csv_row
from repro.core import graph as G
from repro.core.algorithms.connected_components import connected_components
from repro.core.algorithms.legacy import legacy_connected_users
from repro.data import synthetic as S
from repro.utils.compile_cache import enable_compile_cache


def run(out=print):
    rows = []
    for n_users in [2_000, 20_000, 100_000]:
        sets = S.identifier_edge_sets(n_users, n_sets=4, seed=3)
        allsrc = np.concatenate([s for s, _ in sets])
        alldst = np.concatenate([d for _, d in sets])
        g = G.build_coo(allsrc, alldst, n_users, symmetrize=True)

        t_ours, (labels, iters) = time_fn(
            lambda: connected_components(g))
        t_legacy, legacy_labels = time_host(
            legacy_connected_users, sets, n_users, iters=1)
        assert (np.asarray(labels) == legacy_labels).all()

        ratio = t_legacy / t_ours
        rows.append((n_users, t_ours, t_legacy, ratio))
        out(csv_row(f"fig7/unified_cc_u{n_users}", t_ours,
                    f"iters={int(iters)}"))
        out(csv_row(f"fig7/legacy_perset_u{n_users}", t_legacy,
                    f"speedup={ratio:.1f}x(paper:37x)"))

    # ablation (beyond-paper): pointer jumping turns O(diameter) label
    # propagation into O(log d) — decisive on long-chain components
    chain = np.arange(20_000 - 1)
    gch = G.build_coo(chain, chain + 1, 20_000, symmetrize=True)
    t_plain, (_, it_plain) = time_fn(
        lambda: connected_components(gch, accelerated=False,
                                     max_iters=30_000))
    t_jump, (_, it_jump) = time_fn(
        lambda: connected_components(gch, accelerated=True,
                                     max_iters=30_000))
    out(csv_row("fig7/ablation_cc_plain_chain20k", t_plain,
                f"iters={int(it_plain)}"))
    out(csv_row("fig7/ablation_cc_pointer_jump", t_jump,
                f"iters={int(it_jump)};speedup={t_plain/t_jump:.1f}x"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
