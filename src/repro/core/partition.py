"""Edge partitioning for the distributed engine.

The Spark analogue: GraphFrames hash-partitions edge DataFrames across
executors.  On a TPU mesh we pre-partition host-side into fixed-size edge
shards so one BSP superstep is a single statically-shaped `shard_map`:

* **1-D** (``vertex_layout='replicated'``): edges split evenly over the
  ``data`` axis, vertex state replicated.  Per-superstep communication is
  one ``psum``/``pmin`` of the vertex aggregate over ``data``.
* **2-D** (``vertex_layout='sharded'``): the vertex-cut.  The ``model``
  axis owns contiguous destination ranges; each (data, model) shard holds
  edges whose dst falls in its range.  Vertex state is sharded over
  ``model`` and materialized per-superstep with one ``all_gather`` —
  the TPU analogue of GraphX's 2-D vertex-cut shuffle.

Partitioning is host-side numpy (ETL territory), output arrays are laid
out shard-major so ``PartitionSpec`` along the leading dim places each
shard on its device without resharding.  Given the engine's mesh, the
arrays are placed once with the same spec the superstep's ``shard_map``
reads them with (``P('data')``, or ``P(('data', 'model'))`` for the 2-D
layout), so no query moves edges between devices.  Without a mesh they
land on the default device.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.graph import GraphCOO, round_up


@dataclasses.dataclass
class ShardedCOO:
    """Edge shards laid out shard-major along the leading axis.

    ``src/dst/w`` have shape ``[n_shards * e_shard]``; slice ``i`` is
    shard ``i``.  For 2-D partitioning ``n_shards == n_data * n_model``
    and shard ``(d, m)`` sits at index ``d * n_model + m`` (mesh-major
    order for ``PartitionSpec(('data', 'model'))``).
    """

    src: jax.Array
    dst: jax.Array
    w: jax.Array
    n_vertices: int
    n_edges: int
    n_data: int
    n_model: int          # 1 for 1-D partitioning
    e_shard: int
    v_local: int          # vertices owned per model shard (V for 1-D)

    @property
    def vertex_layout(self) -> str:
        return "replicated" if self.n_model == 1 else "sharded"

    @property
    def n_pad(self) -> int:
        """Length of a full vertex-state array (``n_model * v_local``;
        1-D layouts set ``v_local = n_vertices``, so this is V there)."""
        return self.n_model * self.v_local


def _pack_shards(groups, e_shard, sentinel):
    """Stack variable-size edge groups into a padded shard-major array."""
    n = len(groups)
    src = np.full((n, e_shard), sentinel, dtype=np.int32)
    dst = np.full((n, e_shard), sentinel, dtype=np.int32)
    w = np.zeros((n, e_shard), dtype=np.float32)
    for i, (s, d, ww) in enumerate(groups):
        k = s.shape[0]
        src[i, :k], dst[i, :k], w[i, :k] = s, d, ww
    return src.reshape(-1), dst.reshape(-1), w.reshape(-1)


def _place(arrays, mesh, spec):
    """Device arrays: sharded over ``mesh`` by ``spec``, or on the
    default device when there is no mesh."""
    if mesh is None:
        return tuple(jnp.asarray(a) for a in arrays)
    sharding = NamedSharding(mesh, spec)
    return tuple(jax.device_put(a, sharding) for a in arrays)


def partition_1d(g: GraphCOO, n_data: int, pad_multiple: int = 256,
                 mesh=None) -> ShardedCOO:
    """Round-robin edge split over the data axis (vertex state replicated)."""
    src = np.asarray(g.src)[: g.n_edges]
    dst = np.asarray(g.dst)[: g.n_edges]
    w = np.asarray(g.w)[: g.n_edges]
    e_shard = max(pad_multiple, round_up(-(-g.n_edges // n_data), pad_multiple))
    groups = []
    for d in range(n_data):
        sel = slice(d, None, n_data)  # strided → balanced across dst ranges
        groups.append((src[sel], dst[sel], w[sel]))
    s, dd, ww = _place(_pack_shards(groups, e_shard, np.int32(g.n_vertices)),
                       mesh, P("data"))
    return ShardedCOO(
        src=s, dst=dd, w=ww,
        n_vertices=g.n_vertices, n_edges=g.n_edges,
        n_data=n_data, n_model=1, e_shard=e_shard, v_local=g.n_vertices,
    )


def partition_2d(
    g: GraphCOO, n_data: int, n_model: int, pad_multiple: int = 256,
    mesh=None,
) -> ShardedCOO:
    """Vertex-cut: model axis owns dst ranges, data axis splits within."""
    src = np.asarray(g.src)[: g.n_edges]
    dst = np.asarray(g.dst)[: g.n_edges]
    w = np.asarray(g.w)[: g.n_edges]
    v_local = -(-g.n_vertices // n_model)
    owner = np.minimum(dst // v_local, n_model - 1)
    groups = []
    max_block = 0
    for m in range(n_model):
        sel = owner == m
        sm, dm, wm = src[sel], dst[sel], w[sel]
        per_d = []
        for d in range(n_data):
            ss = slice(d, None, n_data)
            per_d.append((sm[ss], dm[ss], wm[ss]))
            max_block = max(max_block, per_d[-1][0].shape[0])
        groups.append(per_d)
    e_shard = max(pad_multiple, round_up(max_block, pad_multiple))
    flat = [groups[m][d] for d in range(n_data) for m in range(n_model)]
    s, dd, ww = _place(_pack_shards(flat, e_shard, np.int32(g.n_vertices)),
                       mesh, P(("data", "model")))
    return ShardedCOO(
        src=s, dst=dd, w=ww,
        n_vertices=g.n_vertices, n_edges=g.n_edges,
        n_data=n_data, n_model=n_model, e_shard=e_shard, v_local=v_local,
    )


def partition(g: GraphCOO, n_data: int, n_model: int = 1, **kw) -> ShardedCOO:
    if n_model <= 1:
        return partition_1d(g, n_data, **kw)
    return partition_2d(g, n_data, n_model, **kw)
