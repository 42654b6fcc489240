"""Drive the graph service end to end on a TPU and check every answer.

    python chip_smoke.py              # one chip: service + kernel phases
    python chip_smoke.py --chips 4    # the distributed engine on 4 chips

The graph is Graph500's Kronecker generator (R-MAT, A/B/C = 0.57/0.19/
0.19, edge factor 16) at scale 22, symmetrized: 4,194,304 vertices and
about 1.25e8 edge slots, made from ``--seed``.

* Service phase: a ``GraphAnalyticsService`` takes the graph through
  ``add_graph`` and answers connected components (labels and count), BFS
  from four sources, PageRank and a k-core count through ``submit`` ->
  ``drain`` -> ``result``.  Its one non-default setting is an interactive
  threshold of 0 s: every ticket tiers batch, so the four BFS tickets
  must fuse into one program.  Every ticket must be done with no
  retries and no dead letters, and every answer must equal a plain
  reference: ``scipy.sparse.csgraph`` for components, and a
  level-synchronous BFS, a k-core peel and a power iteration over a scipy
  CSR matrix.
* Kernel phase: the compiled ``ell_intersect`` Pallas kernel counts the
  triangles of a scale-16 Graph500 graph and must match the jnp path.
* ``--chips 4`` runs only the distributed phase: the same scale-22 graph
  on the distributed engine over a four-chip ``("data",)`` mesh, with
  edge shards placed one per chip, compared against a one-chip
  ``LocalEngine`` run and the references.

The script fails (non-zero exit, no result line) when JAX finds no TPU,
and on any failed ticket or mismatch.  The last line of standard output
is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import scipy.sparse as sp  # noqa: E402
from scipy.sparse import csgraph  # noqa: E402

from repro.core import graph as G  # noqa: E402
from repro.core import planner as P  # noqa: E402
from repro.core.engines import LocalEngine  # noqa: E402
from repro.core.query import GraphQuery  # noqa: E402
from repro.core.service import GraphAnalyticsService  # noqa: E402
from repro.data import synthetic as S  # noqa: E402
from repro.kernels.ell_intersect.ops import MAX_KERNEL_K  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

SERVICE_SCALE = 22
# OrientedELL width ~255, within the kernel's 2048; small enough that
# the jnp comparison path (a vmapped searchsorted) stays short.
KERNEL_SCALE = 16
EDGE_FACTOR = 16
N_SOURCES = 4
K_CORE = 16
PR_ITERS = 10
# So small that the L1 halt (sum |new - old| < tol * V) does not fire
# before PR_ITERS at these sizes: both sides run the same iterations.
PR_TOL = 1e-12
PR_L1_TOL = 1e-5
# Every estimate exceeds 0 s, so every ticket tiers batch and
# same-algorithm tickets fuse.  The default threshold (the active
# profile's) tiers the smoke's queries interactive, and interactive
# tickets run solo.
INTERACTIVE_THRESHOLD_S = 0.0


class SmokeFailure(RuntimeError):
    """A phase produced a wrong answer or an unresolved ticket."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ------------------------------------------------------------------ data

def graph500(scale: int, seed: int) -> G.GraphCOO:
    """Graph500 Kronecker graph, symmetrized and deduplicated."""
    src, dst, n = S.rmat_graph(scale, edge_factor=EDGE_FACTOR, seed=seed,
                               a=0.57, b=0.19, c=0.19)
    return G.build_coo(src, dst, n, symmetrize=True)


def host_edges(coo: G.GraphCOO) -> tuple[np.ndarray, np.ndarray]:
    return (np.asarray(coo.src)[: coo.n_edges],
            np.asarray(coo.dst)[: coo.n_edges])


def host_csr(src: np.ndarray, dst: np.ndarray, n: int) -> sp.csr_matrix:
    """Row u of the result lists the heads of u's out-edges."""
    ones = np.ones(src.shape[0], dtype=np.float32)
    return sp.csr_matrix((ones, (src, dst)), shape=(n, n))


def search_keys(adj: sp.csr_matrix, n: int, seed: int) -> list[int]:
    """Graph500's rule: distinct random vertices with at least one edge."""
    rng = np.random.default_rng(seed)
    return [int(v) for v in rng.choice(np.flatnonzero(np.diff(adj.indptr)),
                                       size=n, replace=False)]


# ------------------------------------------------------------ references

def cc_reference(adj: sp.csr_matrix) -> np.ndarray:
    """Component labels, canonicalized to the component's min vertex id
    (the service's hash-to-min labels)."""
    _, lab = csgraph.connected_components(adj, directed=False)
    first = np.unique(lab, return_index=True)[1]    # min id per label
    return first[lab].astype(np.int32)


def bfs_reference(adj: sp.csr_matrix, source: int) -> np.ndarray:
    """Level-synchronous BFS hop distances (inf = unreachable)."""
    dist = np.full(adj.shape[0], np.inf, dtype=np.float32)
    dist[source] = 0.0
    frontier, level = np.array([source]), 0
    while frontier.size:
        level += 1
        reached = np.zeros(adj.shape[0], dtype=bool)
        reached[adj[frontier].indices] = True
        frontier = np.flatnonzero(reached & np.isinf(dist))
        dist[frontier] = level
    return dist


def pagerank_reference_csr(adj: sp.csr_matrix, alpha: float, tol: float,
                           max_iters: int) -> tuple[np.ndarray, int]:
    """Power iteration in float64, the service's formulation:
    x' = (1-a)/V + a * (A_norm^T x + dangling mass / V), halting once
    sum |x' - x| < tol * V.  Unit edge weights."""
    V = adj.shape[0]
    outdeg = np.diff(adj.indptr).astype(np.float64)
    inv = np.divide(1.0, outdeg, out=np.zeros(V), where=outdeg > 0)
    dangling = outdeg == 0
    at = adj.T.tocsr()
    x = np.full(V, 1.0 / V)
    for it in range(max_iters):
        new = (1 - alpha) / V + alpha * (at @ (x * inv)
                                         + x[dangling].sum() / V)
        if np.abs(new - x).sum() < tol * V:
            return new, it + 1
        x = new
    return x, max_iters


def kcore_size_reference(adj: sp.csr_matrix, k: int) -> int:
    """Vertices left after peeling every vertex with fewer than k live
    in-neighbors, until none is left to peel."""
    deg = np.bincount(adj.indices, minlength=adj.shape[0])   # in-degree
    alive = np.ones(adj.shape[0], dtype=bool)
    while True:
        drop = alive & (deg < k)
        if not drop.any():
            return int(alive.sum())
        alive &= ~drop
        deg -= np.bincount(adj[np.flatnonzero(drop)].indices,
                           minlength=adj.shape[0])


# ---------------------------------------------------------------- phases

def _describe(r) -> str:
    plan = r.meta["plan"]
    fused = r.meta.get("fused")
    return (f"plan engine={plan.engine} variant={plan.variant} "
            f"mode={plan.mode} | ran engine={r.engine} "
            f"variant={r.meta.get('variant')} iters={r.iterations} "
            f"fused_width={fused['batch_size'] if fused else 1}")


def serve(svc: GraphAnalyticsService, graph: str, batches, out=print):
    """Submit each batch of ``(label, query)`` pairs, drain it, and
    return ``{label: QueryResult}``.  The first execution of each
    algorithm includes its compile."""
    results, seen = {}, set()
    for batch in batches:
        tickets = [(label, svc.submit(graph, q)) for label, q in batch]
        t0 = time.perf_counter()
        svc.drain()
        for label, t in tickets:
            require(t.status == "done",
                    f"ticket #{t.ticket_id} ({label}) is {t.status}: "
                    f"{t.error!r}")
        got = [(label, t, svc.result(t)) for label, t in tickets]
        jax.block_until_ready([r.value for _, _, r in got])
        wall = time.perf_counter() - t0
        algorithms = {t.query.algorithm for _, t, _ in got}
        cold = "cold, includes compile" if algorithms - seen else "warm"
        seen |= algorithms
        out(f"batch {[label for label, _ in batch]}: {len(batch)} "
            f"ticket(s) drained in {wall:.3f} s ({cold}; one run, "
            f"not a benchmark)")
        for label, t, r in got:
            out(f"  #{t.ticket_id} {label}: tier={t.tier} {_describe(r)}")
            results[label] = r
    metrics = svc.metrics()
    retry = metrics["retry"]
    out(f"retries={retry['retries']} dead_letters={retry['dead_letters']} "
        f"fused_batches={metrics['counters']['fused_batches']}")
    require(retry["retries"] == 0 and retry["dead_letters"] == 0,
            f"the service retried or dead-lettered: {retry}")
    return results, metrics


def check_cc(labels, ref: np.ndarray, what: str, out=print) -> None:
    bad = int(np.sum(np.asarray(labels) != ref))
    out(f"{what}: {len(np.unique(ref))} components, {bad} labels differ")
    require(bad == 0, f"{what}: {bad} labels differ")


def check_pagerank(ranks, ref: np.ndarray, what: str, out=print) -> None:
    diff = np.abs(np.asarray(ranks, dtype=np.float64) - ref)
    out(f"{what}: L1 distance {diff.sum():.3e}, "
        f"max {diff.max():.3e} (limit L1 {PR_L1_TOL:g})")
    require(diff.sum() <= PR_L1_TOL, f"{what}: L1 {diff.sum():.3e}")


def service_phase(coo: G.GraphCOO, seed: int, out=print) -> None:
    """The service end to end, every answer against a reference."""
    src, dst = host_edges(coo)
    adj = host_csr(src, dst, coo.n_vertices)
    sources = search_keys(adj, N_SOURCES, seed)
    out(f"BFS sources: {sources}")
    svc = GraphAnalyticsService(
        interactive_threshold_s=INTERACTIVE_THRESHOLD_S)
    svc.add_graph("graph500", coo)
    res, metrics = serve(svc, "graph500", [
        [("cc", GraphQuery.connected_components())],
        [("cc_count", GraphQuery.connected_components(count_only=True))],
        [(f"bfs[{s}]", GraphQuery.bfs([s])) for s in sources],
        [("pagerank", GraphQuery.pagerank(max_iters=PR_ITERS,
                                          tol=PR_TOL))],
        [("k_core", GraphQuery.k_core(K_CORE, count_only=True))],
    ], out=out)
    require(metrics["counters"]["fused_batches"] >= 1,
            "no fused batch ran")
    for s in sources:
        fused = res[f"bfs[{s}]"].meta.get("fused")
        require(fused is not None and fused["batch_size"] == N_SOURCES,
                f"bfs[{s}] did not run in the fused batch of "
                f"{N_SOURCES}: {fused}")

    labels = cc_reference(adj)
    check_cc(res["cc"].value, labels, "connected components", out)
    n_comp = len(np.unique(labels))
    out(f"component count: service {res['cc_count'].value}, "
        f"reference {n_comp}")
    require(res["cc_count"].value == n_comp, "component count differs")
    for s in sources:
        got = np.asarray(res[f"bfs[{s}]"].value)
        want = bfs_reference(adj, s)
        bad = int(np.sum(got != want))
        out(f"bfs[{s}]: {int(np.isfinite(want).sum())} reached, depth "
            f"{int(want[np.isfinite(want)].max())}, {bad} distances differ")
        require(bad == 0, f"bfs[{s}]: {bad} distances differ")
    ref_pr, it = pagerank_reference_csr(adj, 0.85, PR_TOL, PR_ITERS)
    out(f"pagerank reference: {it} iterations")
    check_pagerank(res["pagerank"].value, ref_pr, "pagerank", out)
    want = kcore_size_reference(adj, K_CORE)
    out(f"{K_CORE}-core size: service {res['k_core'].value}, "
        f"reference {want}")
    require(res["k_core"].value == want, "k-core size differs")


def kernel_phase(coo: G.GraphCOO, out=print) -> None:
    """Triangles through the ell_intersect Pallas kernel vs the jnp path."""
    kernel = LocalEngine(coo, use_pallas=True)
    width = kernel.oriented.max_out_degree
    out(f"kernel graph: V={coo.n_vertices} E={coo.n_edges} "
        f"OrientedELL width={width} (kernel limit {MAX_KERNEL_K})")
    require(width <= MAX_KERNEL_K,
            f"OrientedELL width {width} > {MAX_KERNEL_K}: the reference "
            f"would run instead of the kernel")
    t0 = time.perf_counter()
    got = kernel.run("triangle_count", variant="intersect")
    wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    want = LocalEngine(coo).run("triangle_count", variant="intersect")
    out(f"triangles: ell_intersect kernel {got.value} in {wall:.3f} s, "
        f"jnp path {want.value} in {time.perf_counter() - t0:.3f} s "
        f"(each cold, includes compile)")
    require(got.value == want.value, "kernel triangle count differs")


def four_chip_mesh():
    devices = jax.devices()
    require(len(devices) >= 4, f"--chips 4 needs 4 devices, found "
            f"{len(devices)}")
    return make_mesh((4,), ("data",), devices=devices[:4])


def _check_placement(sg, mesh, what: str, out=print) -> None:
    want = set(mesh.devices.flat)
    for name in ("src", "dst", "w"):
        shards = getattr(sg, name).addressable_shards
        got = [s.device for s in shards]
        require(len(got) == len(want) and set(got) == want,
                f"{what}.{name} shards sit on {got}, not one per mesh "
                f"device {sorted(want, key=lambda d: d.id)}")
    out(f"{what}: src/dst/w each hold one shard on each of "
        f"{len(want)} devices")


def distributed_phase(coo: G.GraphCOO, mesh, out=print) -> None:
    """The distributed engine on a mesh vs a one-device LocalEngine run
    in the same process and vs the references."""
    svc = GraphAnalyticsService()
    ctx = svc.add_graph("graph500", coo, mesh=mesh,
                        force_engine="distributed")
    res, _ = serve(svc, "graph500", [
        [("cc", GraphQuery.connected_components())],
        [("pagerank", GraphQuery.pagerank(max_iters=PR_ITERS,
                                          tol=PR_TOL))],
    ], out=out)
    for label in ("cc", "pagerank"):
        require(res[label].engine == "distributed",
                f"{label} ran on {res[label].engine}")
    eng = ctx.distributed
    _check_placement(eng.sharded, mesh, "edge shards", out)
    _check_placement(eng.cache["pagerank/normalized"][0], mesh,
                     "pagerank shards", out)

    local = LocalEngine(coo)
    t0 = time.perf_counter()
    lab1 = local.run("connected_components", variant="dense").value
    pr1 = local.run("pagerank", {"max_iters": PR_ITERS,
                                 "tol": PR_TOL}).value
    jax.block_until_ready((lab1, pr1))
    out(f"one-device LocalEngine cc + pagerank: "
        f"{time.perf_counter() - t0:.3f} s (cold, includes compile)")
    check_cc(res["cc"].value, np.asarray(lab1),
             "distributed vs one-device cc", out)
    check_pagerank(res["pagerank"].value, np.asarray(pr1, np.float64),
                   "distributed vs one-device pagerank", out)
    adj = host_csr(*host_edges(coo), coo.n_vertices)
    check_cc(res["cc"].value, cc_reference(adj),
             "distributed cc vs reference", out)
    ref_pr, it = pagerank_reference_csr(adj, 0.85, PR_TOL, PR_ITERS)
    out(f"pagerank reference: {it} iterations")
    check_pagerank(res["pagerank"].value, ref_pr,
                   "distributed pagerank vs reference", out)


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the distributed phase on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t_start = time.perf_counter()

    def out(msg: str) -> None:
        print(f"[{time.perf_counter() - t_start:7.1f} s] {msg}", flush=True)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (its first device is "
              f"{dev.platform!r}); this script measures nothing on other "
              f"devices", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    prof = P.active_calibration()
    out(f"device_kind={dev.device_kind} platform={dev.platform} "
        f"devices={len(jax.devices())} jax={jax.__version__}")
    out(f"compile cache: {cache}")
    out(f"planner profile: source={prof.source} platform={prof.platform}")

    coo = graph500(SERVICE_SCALE, args.seed)
    out(f"graph500 scale {SERVICE_SCALE}: V={coo.n_vertices} "
        f"E={coo.n_edges} edge slots={coo.e_pad} COO bytes={coo.nbytes()}")
    if args.chips == 4:
        mesh = four_chip_mesh()
        distributed_phase(coo, mesh, out)
        used = list(mesh.devices.flat)
    else:
        service_phase(coo, args.seed, out)
        del coo
        kernel_phase(graph500(KERNEL_SCALE, args.seed), out)
        used = [dev]
    for d in used:
        out(f"{d}: peak_bytes_in_use={d.memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(used)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
