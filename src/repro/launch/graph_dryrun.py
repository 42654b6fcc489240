import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# Graph-engine dry-run at PAPER scale: prove the distributed BSP engine
# lowers, partitions and fits for the paper's production workloads on the
# v5e mesh — the reproduction's "would it actually run" artifact.
#
#   multi-account graph: 14.89B vertices, 30.86B edges (heterogeneous)
#   combined connected users: 2.41B vertices, 1.50B edges
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.graph_dryrun [--mesh single|multi]

import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import lax

from repro.launch.mesh import make_production_mesh, n_chips
from repro.utils import roofline as RL
from repro.core.graph import round_up


def lower_pagerank_grid(mesh, n_vertices: int, n_edges: int,
                        n_iters: int = 20, state_bf16: bool = False):
    """Communication-optimal 2-D grid partition (the hillclimbed engine):
    shard (d, m) owns edges with src in range d (data axis) and dst in
    range m (model axis).  Vertex state x is sharded by SRC range over
    'data' — no all_gather of x at all; per superstep the new state
    (computed per dst range) reshards model->data with one all_to_all of
    V/chips per chip.  Collectives drop from O(V) to O(V / n_data)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_data = sizes.get("data", 1) * sizes.get("pod", 1)
    n_model = sizes.get("model", 1)
    e_shard = round_up(-(-n_edges // (n_data * n_model)), 1024)
    v_loc_d = round_up(-(-n_vertices // n_data), 8)     # x by src range
    v_loc_m = round_up(-(-n_vertices // n_model), 8)    # agg by dst range
    V = n_vertices

    data_axes = tuple(a for a in ("pod", "data") if a in sizes)
    edge_spec = P((*data_axes, "model"))
    x_spec = P(data_axes)

    sdt = jnp.bfloat16 if state_bf16 else jnp.float32

    def body(src, dst, w, x_d):
        d_idx = lax.axis_index(data_axes[-1]) if len(data_axes) == 1 else (
            lax.axis_index(data_axes[0]) * sizes["data"]
            + lax.axis_index(data_axes[1]))
        m_idx = lax.axis_index("model")
        src_start = d_idx * v_loc_d
        dst_start = m_idx * v_loc_m

        def one_iter(x_d, _):
            # local src ids -> slice of x owned by this data row
            local_src = jnp.clip(src - src_start, 0, v_loc_d - 1)
            msgs = x_d[local_src].astype(jnp.float32) * w
            local_dst = jnp.where(dst >= V, v_loc_m,
                                  jnp.clip(dst - dst_start, 0, v_loc_m))
            agg = jax.ops.segment_sum(msgs, local_dst,
                                      num_segments=v_loc_m + 1)[:v_loc_m]
            for ax in data_axes:
                agg = lax.psum(agg, ax)                  # combine src rows
            new_m = 0.15 / V + 0.85 * agg                # x by dst range
            # reshard dst-range(model) -> src-range(data): after the data
            # psum, new_m is replicated across data rows, so the chip
            # with m_idx == d_idx holds exactly the slice this chip needs
            # next round.  A masked psum over 'model' delivers it with
            # one ring all-reduce of V/16 floats — O(V/n) instead of the
            # O(V) full gather of the 1-D layout.
            # bf16 wire: PageRank tolerates bf16 state with f32 message
            # accumulation (segment_sum above is f32)
            new_m = new_m.astype(sdt)
            mine = jnp.where(m_idx == d_idx, new_m, jnp.zeros_like(new_m))
            new_d = lax.psum(mine, "model")
            if v_loc_d != v_loc_m:
                new_d = new_d[:v_loc_d]
            return new_d, None

        x_d, _ = lax.scan(one_iter, x_d, None, length=n_iters)
        return x_d

    total_shards = n_data * n_model
    src_sds = jax.ShapeDtypeStruct((total_shards * e_shard,), jnp.int32,
                                   sharding=NamedSharding(mesh, edge_spec))
    w_sds = jax.ShapeDtypeStruct((total_shards * e_shard,), jnp.float32,
                                 sharding=NamedSharding(mesh, edge_spec))
    x_sds = jax.ShapeDtypeStruct((n_data * v_loc_d,), sdt,
                                 sharding=NamedSharding(mesh, x_spec))
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(edge_spec, edge_spec, edge_spec, x_spec),
                       out_specs=x_spec, check_vma=False)
    with mesh:
        lowered = jax.jit(fn).lower(src_sds, src_sds, w_sds, x_sds)
        t0 = time.time()
        compiled = lowered.compile()
        dt = time.time() - t0
    sb = 2 if state_bf16 else 4
    return compiled, {
        "e_shard": e_shard, "v_local": v_loc_m, "compile_s": dt,
        "chips": n_chips(mesh),
        "flops": 2.0 * e_shard + 5.0 * v_loc_d,
        "bytes": e_shard * 12 + (v_loc_d + v_loc_m) * 2 * sb,
        # psum of dst aggregates (f32, ring over data) + masked-psum
        # reshard (state dtype, ring over model) — both O(V/16)
        "coll_bytes": (v_loc_m * 4 * 2 * (n_data - 1) / n_data
                       + v_loc_m * sb * 2 * (n_model - 1) / n_model),
    }


def lower_pagerank(mesh, n_vertices: int, n_edges: int, n_iters: int = 20,
                   vertex_sharded: bool = True):
    """AOT-lower the BSP PageRank superstep loop over abstract edge
    shards of the production scale.  Vertex state is sharded over
    'model' (the 2-D vertex-cut); edges over ('data','model')."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_data = sizes.get("data", 1) * sizes.get("pod", 1)
    n_model = sizes.get("model", 1)
    e_shard = round_up(-(-n_edges // (n_data * n_model)), 1024)
    v_local = round_up(-(-n_vertices // n_model), 8)
    V = n_vertices

    data_axes = tuple(a for a in ("pod", "data") if a in sizes)
    edge_spec = P((*data_axes, "model"))
    state_spec = P("model")

    def body(src, dst, w, x):
        m_idx = lax.axis_index("model")
        start = m_idx * v_local

        def one_iter(x, _):
            full = lax.all_gather(x, "model", tiled=True)
            msgs = full[jnp.clip(src, 0, full.shape[0] - 1)] * w
            local_dst = jnp.where(dst >= V, v_local,
                                  jnp.clip(dst - start, 0, v_local))
            agg = jax.ops.segment_sum(msgs, local_dst,
                                      num_segments=v_local + 1)[:v_local]
            for ax in data_axes:
                agg = lax.psum(agg, ax)
            return 0.15 / V + 0.85 * agg, None

        x, _ = lax.scan(one_iter, x, None, length=n_iters)
        return x

    total_shards = n_data * n_model
    src_sds = jax.ShapeDtypeStruct(
        (total_shards * e_shard,), jnp.int32,
        sharding=NamedSharding(mesh, edge_spec))
    w_sds = jax.ShapeDtypeStruct(
        (total_shards * e_shard,), jnp.float32,
        sharding=NamedSharding(mesh, edge_spec))
    x_sds = jax.ShapeDtypeStruct(
        (n_model * v_local,), jnp.float32,
        sharding=NamedSharding(mesh, state_spec))

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(edge_spec, edge_spec, edge_spec, state_spec),
                       out_specs=state_spec, check_vma=False)
    with mesh:
        lowered = jax.jit(fn).lower(src_sds, src_sds, w_sds, x_sds)
        t0 = time.time()
        compiled = lowered.compile()
        dt = time.time() - t0
    return compiled, {
        "e_shard": e_shard, "v_local": v_local, "compile_s": dt,
        "chips": n_chips(mesh),
        # analytic per-superstep terms, per chip
        "flops": 2.0 * e_shard + 5.0 * v_local,
        "bytes": e_shard * 12 + v_local * 16 + V * 4,   # edges + state + gathered x
        "coll_bytes": (V * 4 * (n_model - 1) / n_model          # all_gather x
                       + v_local * 4 * 2 * (n_data - 1) / n_data),  # psum agg
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--out", default="benchmarks/results/graph_dryrun.json")
    args = ap.parse_args()
    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))

    workloads = {
        # paper scale, MaxAdjacentNodes=uncapped edge counts
        "multi_account_30.9B": dict(n_vertices=14_890_000_000 % (2**31 - 2),
                                    n_edges=30_860_000_000),
        "connected_users_1.5B": dict(n_vertices=2_410_000_000 % (2**31 - 2),
                                     n_edges=1_500_000_000),
    }
    # NOTE: vertex ids are int32 in the engine; the 14.89B-vertex graph
    # exceeds int32 — production would use int64 ids (2x index bytes) or
    # id-compressed partitions.  We lower the int32 variant at the true
    # EDGE scale (the cost driver) and note the id-width adjustment.
    results = {}
    for name, w in workloads.items():
      import functools
      for variant, lower in [
              ("baseline_1d", lower_pagerank),
              ("grid_2d", lower_pagerank_grid),
              ("grid_2d_bf16", functools.partial(lower_pagerank_grid,
                                                 state_bf16=True))]:
        compiled, meta = lower(mesh, w["n_vertices"], w["n_edges"],
                               n_iters=args.iters)
        mem = compiled.memory_analysis()
        per_step = {
            "compute_s": meta["flops"] / RL.PEAK_FLOPS_BF16,
            "memory_s": meta["bytes"] / RL.HBM_BW,
            "collective_s": meta["coll_bytes"] / RL.LINK_BW,
        }
        dom = max(per_step, key=per_step.get)
        results[f"{name}/{variant}"] = {
            **meta, **per_step, "dominant": dom,
            "mem_per_dev_gb": (mem.temp_size_in_bytes
                               + mem.argument_size_in_bytes) / 1e9,
        }
        rr = results[f"{name}/{variant}"]
        print(f"{name}/{variant}: chips={meta['chips']} "
              f"e_shard={meta['e_shard']:,} "
              f"mem/dev={rr['mem_per_dev_gb']:.2f}GB "
              f"compile={meta['compile_s']:.1f}s dominant={dom} "
              f"superstep={max(per_step.values())*1e3:.2f}ms")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2, default=str)


if __name__ == "__main__":
    main()
