"""BSP engine unit tests (single-device path) + distributed-path tests
via subprocess (XLA device-count flags must precede jax init, so the
multi-device cases run in their own interpreter).
"""
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np

from repro.core import graph as G
from repro.core.partition import partition_1d, partition_2d
from repro.core.pregel import PregelSpec, run_pregel
from repro.data import synthetic as S


def test_partition_1d_conserves_edges():
    src, dst = S.user_follow_graph(200, 4.0, seed=0)
    g = G.build_coo(src, dst, 200)
    sg = partition_1d(g, 4)
    s = np.asarray(sg.src)
    valid = s < 200
    assert valid.sum() == g.n_edges


def test_partition_2d_dst_ranges():
    src, dst = S.user_follow_graph(200, 4.0, seed=0)
    g = G.build_coo(src, dst, 200)
    sg = partition_2d(g, 2, 4)
    d = np.asarray(sg.dst).reshape(2 * 4, -1)
    v_local = sg.v_local
    # shard (dd, m) at index dd*4+m holds only dst in range m
    for dd in range(2):
        for m in range(4):
            row = d[dd * 4 + m]
            real = row[row < 200]
            if real.size:
                assert (real // v_local == m).all()


def test_pregel_degree_count():
    """combine=sum with message=1 computes in-degrees."""
    src, dst = S.user_follow_graph(100, 3.0, seed=2)
    g = G.build_coo(src, dst, 100)
    sg = partition_1d(g, 1)
    spec = PregelSpec(
        message=lambda x, w: jnp.ones_like(w),
        combine="sum",
        apply=lambda old, agg, ids, gval: agg,
        identity=0.0,
    )
    state, iters = run_pregel(spec, sg, jnp.zeros(100), max_iters=1)
    ref = np.bincount(np.asarray(g.dst)[:g.n_edges], minlength=100)
    np.testing.assert_allclose(np.asarray(state), ref)


def test_pregel_halt_short_circuits():
    src, dst = S.user_follow_graph(100, 3.0, seed=2)
    g = G.build_coo(src, dst, 100, symmetrize=True)
    sg = partition_1d(g, 1)
    spec = PregelSpec(
        message=lambda lbl, w: lbl,
        combine="min",
        apply=lambda old, agg, ids, gval: jnp.minimum(old, agg),
        identity=np.iinfo(np.int32).max,
        halt=lambda old, new, valid: jnp.logical_not(
            jnp.any(jnp.logical_and(valid, new != old))),
    )
    labels, iters = run_pregel(spec, sg, jnp.arange(100, dtype=jnp.int32),
                               max_iters=100)
    assert int(iters) < 100                  # converged early


MULTI_DEVICE_SCRIPT = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import graph as G
    from repro.core.algorithms.pagerank import pagerank, pagerank_reference
    from repro.core.algorithms.connected_components import (
        connected_components, connected_components_reference)
    from repro.data import synthetic as S
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 2), ('data', 'model'))
    src, dst = S.user_follow_graph(800, 5.0, seed=3)
    g = G.build_coo(src, dst, 800)
    ref, _ = pagerank_reference(np.asarray(g.src)[:g.n_edges],
                                np.asarray(g.dst)[:g.n_edges], 800,
                                max_iters=60, tol=1e-10)
    for nd, nm in [(4, 1), (4, 2)]:
        r, it = pagerank(g, max_iters=60, tol=1e-10, mesh=mesh,
                         n_data=nd, n_model=nm)
        assert float(jnp.max(jnp.abs(r - ref))) < 1e-6, (nd, nm)

    gs = G.build_coo(src, dst, 800, symmetrize=True)
    labref = connected_components_reference(src, dst, 800)
    for nd, nm in [(4, 1), (4, 2)]:
        lab, _ = connected_components(gs, mesh=mesh, n_data=nd, n_model=nm,
                                      accelerated=(nm == 1))
        assert (np.asarray(lab) == labref).all(), (nd, nm)
    print('MULTI_DEVICE_OK')
""")


def test_distributed_pregel_multi_device():
    """1-D and 2-D partitioned engines on an 8-device virtual mesh."""
    r = subprocess.run([sys.executable, "-c", MULTI_DEVICE_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={**__import__('os').environ,
                            "PYTHONPATH": "src"})
    assert "MULTI_DEVICE_OK" in r.stdout, r.stderr[-2000:]


GRID_SCRIPT = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    import numpy as np, jax, jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.core.graph import round_up

    # small PageRank iteration via the 2-D grid scheme vs dense reference
    mesh = make_mesh((4, 2), ('data', 'model'))
    rng = np.random.default_rng(0)
    V, E = 64, 300
    src = rng.integers(0, V, E).astype(np.int32)
    dst = rng.integers(0, V, E).astype(np.int32)
    w = rng.random(E).astype(np.float32)
    n_data, n_model = 4, 2
    v_d, v_m = V // n_data, V // n_model
    # bin edges by (src_range, dst_range); pad shards equal
    shards = [[[] for _ in range(n_model)] for _ in range(n_data)]
    for s_, d_, w_ in zip(src, dst, w):
        shards[s_ // v_d][d_ // v_m].append((s_, d_, w_))
    e_shard = round_up(max(len(c) for row in shards for c in row), 8)
    S = np.full((n_data, n_model, e_shard), V, np.int32)
    D = np.full((n_data, n_model, e_shard), V, np.int32)
    W = np.zeros((n_data, n_model, e_shard), np.float32)
    for i in range(n_data):
        for j in range(n_model):
            for k, (s_, d_, w_) in enumerate(shards[i][j]):
                S[i, j, k], D[i, j, k], W[i, j, k] = s_, d_, w_
    Sf, Df, Wf = (a.reshape(-1) for a in (S, D, W))
    x0 = rng.random(V).astype(np.float32)

    def body(src, dst, w, x_d):
        d_idx = lax.axis_index('data')
        m_idx = lax.axis_index('model')
        local_src = jnp.clip(src - d_idx * v_d, 0, v_d - 1)
        msgs = x_d[local_src] * w
        local_dst = jnp.where(dst >= V, v_m,
                              jnp.clip(dst - m_idx * v_m, 0, v_m))
        agg = jax.ops.segment_sum(msgs, local_dst, num_segments=v_m + 1)[:v_m]
        agg = lax.psum(agg, 'data')
        new_m = 0.15 / V + 0.85 * agg
        mine = jnp.where(m_idx == d_idx % n_model, new_m,
                         jnp.zeros_like(new_m))
        # NOTE: general reshard needs d_idx-th slice; with v_d != v_m we
        # reconstruct from the full state for the test's V (gather fine
        # at this scale; the paper-scale lowering uses the masked psum
        # with n_data == n_model)
        full = lax.all_gather(new_m, 'model', tiled=True)
        new_d = lax.dynamic_slice_in_dim(full, d_idx * v_d, v_d)
        return new_d

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(('data', 'model')),) * 3 + (P('data'),),
                       out_specs=P('data'), check_vma=False)
    with mesh:
        got = jax.jit(fn)(jnp.asarray(Sf), jnp.asarray(Df), jnp.asarray(Wf),
                          jnp.asarray(x0))
    ref = 0.15 / V + 0.85 * np.bincount(
        dst, weights=x0[src] * w, minlength=V)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-6)
    print('GRID_OK')
""")


def test_grid_partition_pagerank_step():
    """2-D grid-partitioned superstep (the graph-engine hillclimb) is
    numerically identical to the dense reference."""
    r = subprocess.run([sys.executable, "-c", GRID_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={**__import__('os').environ,
                            "PYTHONPATH": "src"})
    assert "GRID_OK" in r.stdout, r.stderr[-2000:]
