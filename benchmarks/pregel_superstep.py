"""Superstep-strategy benchmark: dense vs fused vs frontier, per
algorithm and scale, written to ``BENCH_pregel_superstep.json``.

Three measurements, mirroring the three execution strategies the
registry exposes for monoid vertex programs:

  * **variant sweep** — every algorithm that registered superstep
    variants, timed end-to-end through ``Engine.run`` at two scales,
    results asserted bit-identical across strategies (the variants
    contract);
  * **layout microbench** — one superstep of the dense path's
    gather -> [E] messages -> segment-combine against the fused
    ELL gather+combine (no [E] materialization), the XLA-level win the
    fused kernel packages.  This is where "fused beats dense" is
    cleanest: it isolates the memory-layout change from iteration-count
    noise;
  * **frontier scaling** — BFS on a bounded-out-degree graph at 1e6+
    vertices; per-superstep *edge work* computed analytically from the
    converged distance labels (frontier at round r == vertices reached
    at round r-1), reported as a fraction of the dense path's
    rounds x E.

Wall-clock numbers come from a CPU host.  Pallas timings use
interpret mode (a Python-loop emulator) and are labeled as such — they
validate correctness, not TPU performance; the jnp reference paths are
honest CPU timings of the same memory-access patterns.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_fn, csv_row
from repro.core import graph as G
from repro.core import registry as R
from repro.core.algorithms import traversal
from repro.core.engines import LocalEngine
from repro.data import synthetic as S
from repro.kernels.pregel_superstep import fused_superstep_ref
from repro.utils.compile_cache import enable_compile_cache

INTERPRET_NOTE = ("interpret (CPU fallback — not indicative of TPU "
                  "perf)")


def _build(n_vertices: int, symmetric: bool) -> G.GraphCOO:
    src, dst = S.user_follow_graph(n_vertices, 4.0, seed=1)
    keep = src != dst
    return G.build_coo(src[keep], dst[keep], n_vertices,
                       symmetrize=symmetric)


def _bits(x):
    return np.asarray(x).tobytes()


# ----------------------------------------------------------- variant sweep

def variant_sweep(out=print):
    recs = []
    for n_vertices in [2_000, 20_000]:
        graphs = {sym: _build(n_vertices, sym) for sym in (False, True)}
        engines = {sym: LocalEngine(g) for sym, g in graphs.items()}
        for name, defn in R.items():
            variants = sorted(defn.variants or ())
            if "frontier" not in variants:
                continue
            sym = defn.requires_symmetric
            eng, g = engines[sym], graphs[sym]
            params = dict(defn.example_params or {})
            timed, baseline = {}, None
            for var in variants:
                t, r = time_fn(lambda: eng.run(defn, params,
                                               variant=var).value,
                               warmup=1, iters=1)
                timed[var] = t
                if baseline is None:
                    baseline = r
                else:
                    assert _bits(r) == _bits(baseline), (name, var)
                out(csv_row(f"superstep/{name}_{var}_v{n_vertices}", t))
            recs.append({
                "algorithm": name, "n_vertices": n_vertices,
                "n_edges": int(g.n_edges),
                "variants": {v: {"wall_s": timed[v]} for v in timed},
                "bit_identical": True,
                "speedup_frontier_vs_dense":
                    timed["dense"] / timed["frontier"],
            })
    return recs


# ------------------------------------------------------ layout microbench

def layout_microbench(out=print):
    """One superstep, three layouts, honest CPU wall time.

    dense:   gather src state -> [E] messages -> segment-min over [E]
    fused:   ELL gather + masked combine, no [E] tensor (jnp reference
             of the Pallas kernel's access pattern)
    pallas:  same kernel under interpret mode — correctness ping only.
    """
    recs = []
    rng = np.random.default_rng(0)
    for n, deg in [(50_000, 16), (200_000, 8)]:
        # bounded out-degree keeps the ELL width ~Poisson(deg); a
        # power-law graph here would pad every row to the hub degree
        src = np.repeat(np.arange(n, dtype=np.int64), deg)
        dst = rng.integers(0, n, src.shape[0])
        keep = src != dst
        coo = G.build_coo(src[keep], dst[keep], n)
        e = coo.n_edges
        ell = G.build_ell(np.asarray(coo.src)[:e], np.asarray(coo.dst)[:e],
                          n, int(np.bincount(np.asarray(coo.dst)[:e],
                                             minlength=n).max()))
        x = jnp.asarray(rng.standard_normal(n + 1), jnp.float32)

        @jax.jit
        def dense_step(x):
            msgs = x[jnp.clip(coo.src, 0, n)] + coo.w
            return jax.ops.segment_min(msgs, coo.dst,
                                       num_segments=n + 1)[:n]

        @jax.jit
        def fused_step(x):
            return fused_superstep_ref(
                ell.nbr, ell.mask, ell.w, x, message=lambda s, w: s + w,
                op="min", identity=float("inf"))

        t_dense, y_dense = time_fn(dense_step, x)
        t_fused, y_fused = time_fn(fused_step, x)
        # dense segment_min leaves empty segments at +inf max-dtype fill
        # identical to the fused identity fill; compare where defined
        np.testing.assert_array_equal(np.asarray(y_fused),
                                      np.asarray(y_dense))
        out(csv_row(f"superstep/layout_dense_v{n}", t_dense, f"E={e}"))
        out(csv_row(f"superstep/layout_fused_v{n}", t_fused,
                    f"speedup={t_dense / t_fused:.2f}x"))
        recs.append({
            "n_vertices": n, "n_edges": int(e),
            "kmax": int(ell.nbr.shape[1]),
            "dense_segment_combine_s": t_dense,
            "fused_ell_combine_s": t_fused,
            "fused_speedup": t_dense / t_fused,
            "fused_beats_dense": bool(t_fused < t_dense),
        })
    # interpret-mode correctness ping on a tiny shape (labeled)
    nbr = jnp.asarray(rng.integers(0, 256, (256, 128)), jnp.int32)
    mask = jnp.asarray(rng.random((256, 128)) < 0.5)
    w = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    xx = jnp.asarray(rng.standard_normal(256), jnp.float32)
    from repro.kernels.pregel_superstep import fused_superstep
    got = fused_superstep(nbr, mask, w, xx, message=lambda s, w_: s + w_,
                          op="min", identity=float("inf"), use_pallas=True)
    want = fused_superstep_ref(nbr, mask, w, xx,
                               message=lambda s, w_: s + w_,
                               op="min", identity=float("inf"))
    err = float(jnp.max(jnp.abs(got - want)))
    out(csv_row("superstep/pallas_interpret_maxerr", 0.0,
                f"maxerr={err:.2e}"))
    recs.append({"pallas_mode": INTERPRET_NOTE, "max_abs_err": err})
    return recs


# ------------------------------------------------------- frontier scaling

def frontier_scaling(n_vertices=1_000_000, out_degree=8, out=print):
    """BFS at 1e6 V on a bounded-out-degree graph.

    The frontier variant touches only edges leaving vertices whose
    distance changed last round; with converged labels in hand the
    per-round frontier (and its out-edge count) is exact analytics, no
    timing noise.  Wall clocks for dense vs frontier ride along.
    """
    rng = np.random.default_rng(42)
    src = np.repeat(np.arange(n_vertices, dtype=np.int64), out_degree)
    dst = rng.integers(0, n_vertices, src.shape[0])
    keep = src != dst
    g = G.build_coo(src[keep], dst[keep], n_vertices)
    eng = LocalEngine(g)
    spec = traversal._BFS_SPEC
    init = jnp.full((eng.sharded.n_pad,), jnp.inf,
                    jnp.float32).at[0].set(0.0)
    max_iters = 64
    t_dense, (d_dense, it_dense) = time_fn(
        lambda: eng.run_superstep(spec, init, max_iters, variant="dense"))
    t_front, (d_front, it_front) = time_fn(
        lambda: eng.run_superstep(spec, init, max_iters,
                                  variant="frontier"))
    assert _bits(d_dense[:n_vertices]) == _bits(d_front[:n_vertices])
    assert int(it_dense) == int(it_front)
    iters = int(it_dense)

    dist = np.asarray(d_dense[:n_vertices])
    out_deg = np.bincount(np.asarray(g.src)[: g.n_edges],
                          minlength=n_vertices)
    finite = np.isfinite(dist)
    rounds = dist[finite].astype(np.int64)
    # frontier at round r == vertices first reached at round r-1 (the
    # sources at round 0); its message work is their out-edge total
    frontier_sizes = np.bincount(rounds, minlength=iters)
    frontier_edges = np.bincount(rounds, weights=out_deg[finite],
                                 minlength=iters)
    dense_edges = float(g.n_edges) * iters
    touched = float(frontier_edges[:iters].sum())
    out(csv_row(f"superstep/frontier_bfs_v{n_vertices}", t_front,
                f"edge_work={touched / dense_edges:.3f}x_dense"))
    out(csv_row(f"superstep/dense_bfs_v{n_vertices}", t_dense,
                f"iters={iters}"))
    return {
        "algorithm": "bfs", "n_vertices": n_vertices,
        "n_edges": int(g.n_edges), "iterations": iters,
        "bit_identical": True,
        "dense": {"wall_s": t_dense,
                  "edges_touched": dense_edges},
        "frontier": {"wall_s": t_front,
                     "edges_touched": touched,
                     "per_round_frontier":
                         frontier_sizes[:iters].astype(int).tolist(),
                     "per_round_edges":
                         frontier_edges[:iters].astype(int).tolist()},
        "frontier_edge_work_fraction": touched / dense_edges,
    }


def run(out=print):
    """benchmarks.run entry point — the cheap subset (no 1e6-V build)."""
    variant_sweep(out=out)
    layout_microbench(out=out)
    return []


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_pregel_superstep.json")
    ap.add_argument("--scale", type=int, default=1_000_000,
                    help="vertex count for the frontier-scaling BFS")
    args = ap.parse_args(argv)
    report = {
        "benchmark": "pregel_superstep",
        "host": {
            "platform": jax.devices()[0].platform,
            "timing_note": (
                "jnp-reference wall clocks on a CPU host; Pallas rows "
                "are " + INTERPRET_NOTE),
        },
        "variant_sweep": variant_sweep(),
        "layout_microbench": layout_microbench(),
        "frontier_scaling": frontier_scaling(n_vertices=args.scale),
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
