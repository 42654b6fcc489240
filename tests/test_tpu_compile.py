"""Compile the main path's device programs for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, which is installed next to
JAX, compiles for a v5e:2x2 topology that is described but not attached,
and refuses what the chip would refuse (an unsupported Pallas lowering, a
program that does not fit HBM).  The topology is described inside a
fixture, never while a module is imported: only one process at a time may
load the TPU library, and pytest-xdist workers all import this file.
Kernels get ``interpret=False`` explicitly, because the ops wrappers see
the CPU here and would pick interpret mode; ``segment_runs`` picks its
kernel where the program is lowered, so it needs no flag.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import graph as G
from repro.core.algorithms.connected_components import connected_components
from repro.core.algorithms.traversal import _BFS_SPEC
from repro.core.partition import ShardedCOO
from repro.core.algorithms.triangles import _ADJACENCY_SPEC
from repro.core.pregel import PregelSpec, batched_spec, run_pregel
from repro.kernels.ell_intersect.kernel import ell_intersect_pallas
from repro.kernels.segment_runs import segment_runs
from repro.launch.mesh import make_mesh

HBM_BYTES = 16 * 10**9          # one v5e chip
# chip_smoke.py's service graph: Graph500 scale 22, edge factor 16,
# symmetrized.  Dedup leaves fewer slots than this upper bound.
V_SMOKE = 1 << 22
E_SMOKE = 2 * 16 * V_SMOKE


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU compile written to the persistent cache cannot be read back
    # without a chip; keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("k", [128, 1024, 2048])
def test_ell_intersect_kernel_compiles(topo, k):
    one_chip = SingleDeviceSharding(topo.devices[0])
    e = 1 << 18
    rows = jax.ShapeDtypeStruct((k, e), jnp.int32, sharding=one_chip)
    compiled = ell_intersect_pallas.lower(
        rows, rows, sentinel=1 << 20, k_valid=k, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", ["float32", "int32", "uint32",
                                   "bfloat16"])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_segment_runs_kernel_compiles(topo, op, dtype):
    """Every monoid and message dtype a dense spec may send lowers to the
    Mosaic kernel (uint32 is scanned as int32 bits: Mosaic has no
    unsigned min/max)."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    e, v = 1 << 16, 1 << 12
    args = (jax.ShapeDtypeStruct((e,), jnp.dtype(dtype), sharding=one_chip),
            jax.ShapeDtypeStruct((e,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((v + 1,), jnp.int32, sharding=one_chip))
    compiled = jax.jit(segment_runs, static_argnums=3).lower(
        *args, op).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _edge_shapes(sharding, n_slots):
    ids = jax.ShapeDtypeStruct((n_slots,), jnp.int32, sharding=sharding)
    w = jax.ShapeDtypeStruct((n_slots,), jnp.float32, sharding=sharding)
    return ids, ids, w


def _cc_program(n_data, mesh):
    def program(src, dst, w):
        g = G.GraphCOO(src, dst, w, V_SMOKE, E_SMOKE, symmetric=True)
        sg = ShardedCOO(src, dst, w, n_vertices=V_SMOKE, n_edges=E_SMOKE,
                        n_data=n_data, n_model=1,
                        e_shard=E_SMOKE // n_data, v_local=V_SMOKE)
        return connected_components(g, mesh=mesh, sharded=sg)
    return jax.jit(program)


def test_dense_cc_superstep_compiles_at_smoke_scale(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = _cc_program(1, None).lower(
        *_edge_shapes(one_chip, E_SMOKE)).compile()
    assert _total_bytes(compiled) < HBM_BYTES


def test_fused_bfs_superstep_compiles_at_smoke_scale(topo):
    """The smoke's four BFS tickets fuse into one dense program over
    [V, 4] distance state."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def program(src, dst, w, init):
        sg = ShardedCOO(src, dst, w, n_vertices=V_SMOKE, n_edges=E_SMOKE,
                        n_data=1, n_model=1, e_shard=E_SMOKE,
                        v_local=V_SMOKE)
        return run_pregel(batched_spec(_BFS_SPEC), sg, init, V_SMOKE)

    init = jax.ShapeDtypeStruct((V_SMOKE, 4), jnp.float32,
                                sharding=one_chip)
    compiled = jax.jit(program).lower(
        *_edge_shapes(one_chip, E_SMOKE), init).compile()
    assert _total_bytes(compiled) < HBM_BYTES


def test_sharded_cc_superstep_compiles_on_four_chips(topo):
    mesh = make_mesh((4,), ("data",), devices=np.array(topo.devices))
    edges = NamedSharding(mesh, P("data"))
    compiled = _cc_program(4, mesh).lower(
        *_edge_shapes(edges, E_SMOKE)).compile()
    hlo = compiled.as_text()
    assert "all-reduce" in hlo                # the per-superstep pmin/psum
    assert _total_bytes(compiled) < HBM_BYTES  # per device


def _offsets_program(job, with_offsets):
    def program(src, dst, w, offsets, init):
        sg = ShardedCOO(src, dst, w, n_vertices=V_SMOKE, n_edges=E_SMOKE,
                        n_data=1, n_model=1, e_shard=E_SMOKE,
                        v_local=V_SMOKE,
                        in_offsets=offsets if with_offsets else None)
        if job == "cc":
            g = G.GraphCOO(src, dst, w, V_SMOKE, E_SMOKE, symmetric=True)
            return connected_components(g, sharded=sg)
        return run_pregel(batched_spec(_BFS_SPEC), sg, init, V_SMOKE)
    return jax.jit(program)


@pytest.mark.parametrize("job", ["cc", "batched_bfs"])
def test_runs_combine_superstep_compiles_at_smoke_scale(topo, job):
    """With run offsets the dense superstep of ``[E]`` messages combines
    through the segment_runs kernel: Mosaic lowers it, no scatter is
    left, and the program fits one chip.  Batched BFS's ``[E, 4]``
    messages keep the scatter: the same program as without offsets."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    offsets = jax.ShapeDtypeStruct((V_SMOKE + 1,), jnp.int32,
                                   sharding=one_chip)
    init = jax.ShapeDtypeStruct((V_SMOKE, 4), jnp.float32,
                                sharding=one_chip)
    args = (*_edge_shapes(one_chip, E_SMOKE), offsets, init)
    compiled = _offsets_program(job, True).lower(*args).compile()
    hlo = compiled.as_text()
    assert _total_bytes(compiled) < HBM_BYTES
    if job == "cc":
        assert "tpu_custom_call" in hlo
        assert " scatter(" not in hlo
    else:
        assert "tpu_custom_call" not in hlo
        without = _offsets_program(job, False).lower(*args).compile()
        assert _total_bytes(compiled) == _total_bytes(without)


@pytest.mark.parametrize("dtype", ["float32", "uint32"])
def test_runs_combine_keeps_the_scatters_memory(topo, dtype):
    """A sum superstep of ``[E]`` messages at smoke scale reserves no
    more with run offsets than with the scatter, beyond the offsets
    themselves: the kernel's output takes the message buffer's place and
    the end read gathers at most ``READ_CHUNK`` rows at a time."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    spec = PregelSpec(message=lambda s, w: s, combine="sum",
                      apply=lambda old, agg, ids, gval: agg, identity=0)

    def program(with_offsets):
        def run(src, dst, w, offsets, init):
            sg = ShardedCOO(src, dst, w, n_vertices=V_SMOKE,
                            n_edges=E_SMOKE, n_data=1, n_model=1,
                            e_shard=E_SMOKE, v_local=V_SMOKE,
                            in_offsets=offsets if with_offsets else None)
            return run_pregel(spec, sg, init, 3)
        return jax.jit(run)

    args = (*_edge_shapes(one_chip, E_SMOKE),
            jax.ShapeDtypeStruct((V_SMOKE + 1,), jnp.int32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((V_SMOKE,), jnp.dtype(dtype),
                                 sharding=one_chip))
    runs = program(True).lower(*args).compile()
    scatter = program(False).lower(*args).compile()
    assert "tpu_custom_call" in runs.as_text()
    # the offsets are an argument of both programs
    assert _total_bytes(runs) <= 1.01 * _total_bytes(scatter)


def test_triangle_bitset_memory_unchanged_by_offsets(topo):
    """The bitset triangle count's ``[E, V/32 + 1]`` rows keep the
    scatter on shards with run offsets, so its first superstep reserves
    what it did without them (a row-wide runs combine would add copies
    that grow with the row)."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    v, e = 8192, 2 * 16 * 8192
    words = -(-v // 32) + 1

    def program(with_offsets):
        def run(src, dst, w, offsets, init):
            sg = ShardedCOO(src, dst, w, n_vertices=v, n_edges=e,
                            n_data=1, n_model=1, e_shard=e, v_local=v,
                            in_offsets=offsets if with_offsets else None)
            return run_pregel(_ADJACENCY_SPEC, sg, init, 1)
        return jax.jit(run)

    args = (*_edge_shapes(one_chip, e),
            jax.ShapeDtypeStruct((v + 1,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((v, words), jnp.uint32, sharding=one_chip))
    runs = program(True).lower(*args).compile()
    scatter = program(False).lower(*args).compile()
    assert "tpu_custom_call" not in runs.as_text()
    assert _total_bytes(runs) == _total_bytes(scatter)
