"""BSP vertex-centric superstep engine — the Spark/GraphFrames analogue.

One Pregel superstep (Malewicz et al., the model GraphFrames ultimately
lowers to) maps onto a TPU mesh as::

    gather   : read source-vertex state along edges        (local gather /
               all_gather over the ``model`` axis when vertex-sharded)
    message  : per-edge compute                            (VPU)
    combine  : segment-reduce messages to destinations     (local)
    shuffle  : merge partial aggregates across edge shards (psum/pmin/pmax
               over the ``data`` axis — Spark's shuffle becomes one ring
               collective)
    apply    : per-vertex state update                     (VPU)

Everything is statically shaped: padded edges carry the sentinel vertex id
and are dropped at the segment-combine.  Convergence is decided *inside*
the jitted loop with a global ``psum`` of per-shard change counts, so a
whole multi-superstep algorithm (PageRank, hash-to-min CC) is a single
XLA program — the property that makes the distributed engine orders of
magnitude faster than a dataflow engine that materializes every round.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from collections import OrderedDict
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.partition import ShardedCOO

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class PregelSpec:
    """One vertex program.

    message : (src_state[E], w[E]) -> msg[E] or msg[E, M]; with
              ``needs_dst_state`` the signature is
              (src_state, w, dst_state) — an *edge* program that can read
              both endpoints (triangle counting intersects neighborhoods
              this way).
    combine : the message monoid.  Either a single op ('sum'|'min'|'max')
              applied to the whole message, or a tuple of ``(op, width)``
              column groups for *structured* messages: the message's last
              axis is split into contiguous groups, each combined with its
              own monoid (label propagation sends C sum-combined weight
              channels next to C min-combined label channels in one
              superstep).
    apply   : (old_state[Vl], agg, vertex_ids[Vl], gval) -> new_state
    identity: identity element of the monoid — a scalar, or a tuple of
              per-group identities matching a grouped ``combine`` (fills
              vertices with no incoming message)
    halt    : optional (old, new, valid[Vl]) -> bool array (per-shard
              "locally converged"); None runs exactly ``max_iters``.
    global_value : optional (state[Vl], ids, valid) -> scalar (or small
              array) partial; summed across vertex shards and fed to
              ``apply`` as ``gval`` (PageRank uses this for the
              dangling-mass redistribution — the one pattern a pure
              message-passing model can't express).
    global_over_agg : compute ``global_value`` over the *new* combined
              aggregate instead of the pre-superstep state — the hook a
              same-superstep normalization needs (HITS divides the fresh
              hub/authority sums by their own L2 norms inside the loop,
              making the whole algorithm one XLA program).

    Execution-strategy declarations (all optional; defaults keep the
    dense gather/segment-combine path, which remains the correctness
    oracle):

    elementwise_message : the message is pure elementwise jnp code in
              ``(src_state, w)`` and shape-polymorphic — callable on
              ``[E]`` edge vectors (dense path) and ``[V, K]`` gathered
              ELL tiles (fused kernel) alike.  Prerequisite for the
              fused and frontier variants.
    frontier_mode : how sparse-active supersteps may skip inactive
              vertices.  ``'monotone'`` (min/max combines whose apply
              folds the aggregate into state with the same monoid —
              BFS/SSSP/CC): a source unchanged since round t already
              delivered its identical message then, and the fold made
              it permanent, so omitting it is a no-op.  ``'delta'``
              (sum combines with integer-valued messages — k-core): a
              running aggregate is carried and changed sources scatter
              ``msg(new) - msg(old)``.  Both are *exact* — bit-identical
              trajectories to the dense path — under those conditions.
    frontier_init : optional ``state -> bool[V]`` activity predicate
              for the first frontier (monotone mode); default is
              ``state != identity``.  Must be a module-level callable
              (it keys jit caches).
    message_dtype : reduced-precision message channel ('bfloat16' /
              'float16').  Messages are cast to this dtype right after
              the edge program, before the combine — halving message
              traffic.  min/max monoids always tolerate this (per-
              message rounding only); sum monoids reorder inexact
              accumulation and require ``allow_inexact_sum``.
    allow_inexact_sum : explicit opt-in for ``message_dtype`` on a sum
              monoid (the result is then approximate).

    Vertex state may be 1-D ``[Vl]`` or N-D ``[Vl, ...]`` (triangle
    counting keeps a packed neighborhood bitset per vertex); padding-slot
    freezing broadcasts over the trailing axes.
    """

    message: Callable[..., Array]
    combine: object
    apply: Callable[[Array, Array, Array, Array], Array]
    identity: object
    halt: Optional[Callable[[Array, Array, Array], Array]] = None
    global_value: Optional[Callable[[Array, Array, Array], Array]] = None
    needs_dst_state: bool = False
    global_over_agg: bool = False
    elementwise_message: bool = False
    frontier_mode: Optional[str] = None
    frontier_init: Optional[Callable[[Array], Array]] = None
    message_dtype: Optional[str] = None
    allow_inexact_sum: bool = False


@dataclasses.dataclass(frozen=True)
class SuperstepVariant:
    """A planner-visible execution strategy for a PregelSpec runner.

    Registered in an AlgorithmDef's ``variants`` mapping next to the
    dense spec (the triangle_count bitset-vs-intersect idiom), so the
    cost model picks dense vs fused vs frontier per graph.  Engines
    dispatch it through ``Engine.run_superstep`` — which falls back to
    the dense path when the strategy's preconditions don't hold on that
    engine, keeping the variants contract (identical results on every
    variant) unconditional; the result's ``meta['variant']`` names the
    variant that ran.
    """

    spec: PregelSpec
    mode: str  # 'fused' | 'frontier'


def check_precision(spec: PregelSpec) -> None:
    """Validate the reduced-precision declaration of a spec.

    min/max monoids are always safe (rounding is per-message; the
    combine itself is exact in any order).  Inexact sums are only
    allowed behind the explicit opt-in, and structured (grouped-monoid)
    messages can't take a single channel dtype at all.
    """
    if spec.message_dtype is None:
        return
    if isinstance(spec.combine, tuple):
        raise ValueError(
            "message_dtype: structured (grouped-monoid) messages do not "
            "support a reduced-precision channel")
    if spec.combine == "sum" and not spec.allow_inexact_sum:
        raise ValueError(
            "message_dtype with a 'sum' monoid accumulates rounding "
            "error; opt in explicitly with allow_inexact_sum=True")


def reduced_precision(spec: PregelSpec, dtype,
                      allow_inexact_sum: Optional[bool] = None) -> PregelSpec:
    """Derive a spec whose message channel runs in ``dtype``."""
    s = dataclasses.replace(
        spec, message_dtype=jnp.dtype(dtype).name,
        allow_inexact_sum=(spec.allow_inexact_sum
                           if allow_inexact_sum is None
                           else allow_inexact_sum))
    check_precision(s)
    return s


def converged_halt(old, new, valid):
    """The standard fixpoint predicate: no valid vertex changed state.
    Shared by every to-convergence vertex program (CC, traversal, LPA,
    k-core peeling)."""
    return jnp.logical_not(jnp.any(jnp.logical_and(valid, new != old)))


@functools.lru_cache(maxsize=64)
def batched_spec(spec: PregelSpec) -> PregelSpec:
    """Lift a scalar vertex program onto a trailing batch axis.

    The returned spec runs K independent instances of ``spec`` as *one*
    program over state ``[Vl, K]`` — the fused-batch substrate of the
    service layer (K BFS frontiers with different sources share every
    gather, segment-combine and collective of every superstep).  Each
    column's arithmetic is the unbatched program's, element for element
    (vmap only widens the ops), and the monoid combines are exact
    per-column, so column ``k`` of the fused result is bit-identical to
    running instance ``k`` alone.  The fused ``halt`` is the AND over
    columns; converged columns sit at their fixpoint (apply is a no-op
    there) while stragglers finish.

    Memoized (bounded) so repeated fusions of the same program hit the
    jit cache.  Structured (grouped-monoid) messages split columns
    positionally and cannot carry a trailing batch axis — rejected up
    front.
    """
    if isinstance(spec.combine, tuple):
        raise ValueError(
            "batched_spec: structured (grouped-monoid) messages cannot "
            "be lifted onto a batch axis")
    msg_axes = (-1, None, -1) if spec.needs_dst_state else (-1, None)
    message = jax.vmap(spec.message, in_axes=msg_axes, out_axes=-1)
    # with a global_value the per-column scalars arrive as a trailing-K
    # vector and each column's apply reads its own entry
    gval_axis = None if spec.global_value is None else -1
    apply_ = jax.vmap(spec.apply, in_axes=(-1, -1, None, gval_axis),
                      out_axes=-1)

    halt = None
    if spec.halt is not None:
        per_col = jax.vmap(spec.halt, in_axes=(-1, -1, None))

        def halt(old, new, valid):
            return jnp.all(per_col(old, new, valid))

    gval = None
    if spec.global_value is not None:
        per_col_g = jax.vmap(spec.global_value, in_axes=(-1, None, None),
                             out_axes=-1)

        def gval(state, ids, valid):
            return per_col_g(state, ids, valid)

    # activity is per-vertex: a vertex is active if ANY column is (the
    # frontier loop reduces trailing axes with `any` after this)
    frontier_init = None
    if spec.frontier_init is not None:
        frontier_init = jax.vmap(spec.frontier_init, in_axes=-1,
                                 out_axes=-1)

    return PregelSpec(
        message=message, combine=spec.combine, apply=apply_,
        identity=spec.identity, halt=halt, global_value=gval,
        needs_dst_state=spec.needs_dst_state,
        global_over_agg=spec.global_over_agg,
        elementwise_message=spec.elementwise_message,
        frontier_mode=spec.frontier_mode,
        frontier_init=frontier_init,
        message_dtype=spec.message_dtype,
        allow_inexact_sum=spec.allow_inexact_sum)


_SEG = {
    "sum": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
}


def _psum_like(x: Array, op: str, axis) -> Array:
    if op == "sum":
        return lax.psum(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    raise ValueError(op)


def _local_combine(msgs, dst, n_vertices, v_local, start, op, identity):
    """Segment-combine messages into the locally-owned vertex range.

    Grouped ``op`` splits the message's last axis into ``(op, width)``
    column groups, each combined under its own monoid.
    """
    if isinstance(op, tuple):
        parts, c0 = [], 0
        for (o, width), ident in zip(op, identity):
            parts.append(_local_combine(msgs[..., c0:c0 + width], dst,
                                        n_vertices, v_local, start, o, ident))
            c0 += width
        return jnp.concatenate(parts, axis=-1)
    local_dst = jnp.where(dst >= n_vertices, v_local, dst - start)
    local_dst = jnp.clip(local_dst, 0, v_local)
    agg = _SEG[op](msgs, local_dst, num_segments=v_local + 1)[:v_local]
    if op in ("min", "max"):
        # segment_min/max give +/-inf (or int extremes) for empty segments;
        # normalize to the declared identity.
        no_msg = _SEG["sum"](jnp.ones_like(msgs, dtype=jnp.int32),
                             local_dst, num_segments=v_local + 1)[:v_local] == 0
        agg = jnp.where(no_msg, jnp.asarray(identity, agg.dtype), agg)
    return agg


def _shard_combine(agg, op, axis):
    """Cross-shard merge of partial aggregates (grouped ops column-wise)."""
    if isinstance(op, tuple):
        parts, c0 = [], 0
        for o, width in op:
            parts.append(_psum_like(agg[..., c0:c0 + width], o, axis))
            c0 += width
        return jnp.concatenate(parts, axis=-1)
    return _psum_like(agg, op, axis)


# Bounded LRU of jitted superstep programs.  Keys are *structural*:
# meshes enter as (axis names/types, shape, device ids), never as the
# Mesh object — unbounded Mesh-keyed entries used to pin device state
# for the life of the process.  A cached *mesh-path* program still
# closes over the mesh it was built with (shard_map needs one), so a
# dead Mesh can linger until its entry ages out of the LRU; the bound
# is what turns that from a leak into a window.
_JIT_CACHE: OrderedDict = OrderedDict()
JIT_CACHE_MAX = 64
# The service runtime executes on worker threads (one per engine); the
# LRU's get/move_to_end/popitem sequences are not atomic under free
# threading, so guard them.  Building a missed program happens outside
# the lock — two threads may race to compile the same key and the loser
# simply overwrites with an equivalent entry.
_JIT_CACHE_LOCK = threading.Lock()


def _mesh_cache_key(mesh):
    if mesh is None:
        return None
    # axis_types distinguishes semantically different meshes over the
    # same devices (Auto vs Explicit axes) on jax versions that have it
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat),
            str(getattr(mesh, "axis_types", None)))


def _jit_cache_get(key):
    """Returns (cached fn or None, hashable key or None)."""
    with _JIT_CACHE_LOCK:
        try:
            fn = _JIT_CACHE.get(key)
        except TypeError:          # unhashable spec (closure consts)
            return None, None
        if fn is not None:
            _JIT_CACHE.move_to_end(key)
        return fn, key


def _jit_cache_put(key, fn) -> None:
    if key is None:
        return
    with _JIT_CACHE_LOCK:
        _JIT_CACHE[key] = fn
        while len(_JIT_CACHE) > JIT_CACHE_MAX:
            _JIT_CACHE.popitem(last=False)


def run_pregel(
    spec: PregelSpec,
    sg: ShardedCOO,
    init_state: Array,
    max_iters: int,
    mesh: Optional[Mesh] = None,
    axis_data: str = "data",
    axis_model: str = "model",
):
    """Run the vertex program to convergence (or ``max_iters``).

    Returns ``(final_state [V or n_model*v_local], iterations_run)``.
    With ``mesh=None`` runs the same program on one device (the engine the
    planner picks for medium graphs still shares this code path).
    """
    check_precision(spec)
    V = sg.n_vertices
    v_local = sg.v_local
    sharded = sg.vertex_layout == "sharded"

    def body(src, dst, w, state):
        """Executes per-device under shard_map (or directly, single device)."""
        dist = mesh is not None
        if sharded:
            m_idx = lax.axis_index(axis_model) if dist else 0
            start = m_idx * v_local
        else:
            start = 0
        ids = start + jnp.arange(v_local, dtype=jnp.int32)
        valid = ids < V

        def one_iter(state):
            if sharded and dist:
                full = lax.all_gather(state, axis_model, tiled=True)
            else:
                full = state
            src_state = full[jnp.clip(src, 0, full.shape[0] - 1)]
            if spec.needs_dst_state:
                dst_state = full[jnp.clip(dst, 0, full.shape[0] - 1)]
                msgs = spec.message(src_state, w, dst_state)
            else:
                msgs = spec.message(src_state, w)
            if spec.message_dtype is not None:
                msgs = msgs.astype(spec.message_dtype)
            agg = _local_combine(msgs, dst, V, v_local, start,
                                 spec.combine, spec.identity)
            if dist:
                agg = _shard_combine(agg, spec.combine, axis_data)
            if spec.global_value is not None:
                g_src = agg if spec.global_over_agg else state
                gval = spec.global_value(g_src, ids, valid)
                if sharded and dist:
                    gval = lax.psum(gval, axis_model)
            else:
                gval = jnp.float32(0.0)
            new = spec.apply(state, agg, ids, gval)
            vmask = valid.reshape(valid.shape + (1,) * (new.ndim - 1))
            new = jnp.where(vmask, new, state)  # freeze padding slots
            return new

        if spec.halt is None:
            def fori(_, s):
                return one_iter(s)
            final = lax.fori_loop(0, max_iters, fori, state)
            return final, jnp.int32(max_iters)

        def cond(carry):
            _, i, done = carry
            return jnp.logical_and(i < max_iters, jnp.logical_not(done))

        def step(carry):
            s, i, _ = carry
            new = one_iter(s)
            conv_local = spec.halt(s, new, valid)
            not_conv = jnp.logical_not(conv_local).astype(jnp.int32)
            if dist:
                axes = (axis_data, axis_model) if sharded else (axis_data,)
                not_conv = lax.psum(not_conv, axes)
            return new, i + 1, not_conv == 0

        final, iters, _ = lax.while_loop(
            cond, step, (state, jnp.int32(0), jnp.array(False)))
        return final, iters

    # jit-cache: repeated queries on the same engine must not re-trace
    # (the 'consistent query performance' property of the local engine)
    key = (spec, max_iters, _mesh_cache_key(mesh), axis_data, axis_model,
           V, v_local, sg.n_data, sg.n_model, sg.e_shard,
           init_state.shape, str(init_state.dtype))
    fn, key = _jit_cache_get(key)
    if mesh is None:
        # Single-device: shards concatenated — treat as one big shard.
        # (2-D vertex-sharded layouts only make sense on a mesh.)
        assert not sharded, "vertex-sharded layout requires a mesh"
        if fn is None:
            fn = jax.jit(body)
            _jit_cache_put(key, fn)
        return fn(sg.src, sg.dst, sg.w, init_state)

    if fn is None:
        edge_spec = P((axis_data, axis_model)) if sharded else P(axis_data)
        state_spec = P(axis_model) if sharded else P()
        fn = jax.jit(jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(edge_spec, edge_spec, edge_spec, state_spec),
            out_specs=(state_spec, P()),
            check_vma=False,
        ))
        _jit_cache_put(key, fn)
    return fn(sg.src, sg.dst, sg.w, init_state)


def _check_superstep_spec(spec: PregelSpec, what: str) -> None:
    check_precision(spec)
    if not spec.elementwise_message:
        raise ValueError(f"{what}: spec does not declare "
                         "elementwise_message")
    if spec.needs_dst_state:
        raise ValueError(f"{what}: two-endpoint edge programs are "
                         "dense-path only")
    if isinstance(spec.combine, tuple):
        raise ValueError(f"{what}: structured (grouped-monoid) messages "
                         "are dense-path only")


def run_pregel_fused(
    spec: PregelSpec,
    ell,
    init_state: Array,
    max_iters: int,
    use_pallas: bool = False,
    block_rows: int = 512,
):
    """Run the vertex program with the fused-superstep kernel.

    Same contract and return value as ``run_pregel`` on a single
    device, but each superstep is one pass over the in-neighbor ELL
    layout (``kernels/pregel_superstep``): gather src state → edge
    program → monoid combine into dst rows, with no [E] message tensor
    and no separate segment-combine launch.  Bit-identical to the dense
    path for min/max monoids and integer-valued sums (the only specs
    registered with this variant).

    ``ell`` is the uncapped ``direction='in'`` layout over the full
    graph (every edge retained; the engine builds and caches it).
    """
    from repro.kernels.pregel_superstep import ops as superstep_ops

    _check_superstep_spec(spec, "run_pregel_fused")
    V = ell.n_vertices
    if init_state.shape[0] != V:
        raise ValueError("run_pregel_fused: state must be unpadded [V]")

    def body(nbr, mask, w, state):
        ids = jnp.arange(V, dtype=jnp.int32)
        valid = ids < V        # all True; uniform halt/global signature

        def one_iter(state):
            agg = superstep_ops.fused_superstep(
                nbr, mask, w, state, message=spec.message,
                op=spec.combine, identity=spec.identity,
                message_dtype=spec.message_dtype, use_pallas=use_pallas,
                block_rows=block_rows)
            if spec.global_value is not None:
                g_src = agg if spec.global_over_agg else state
                gval = spec.global_value(g_src, ids, valid)
            else:
                gval = jnp.float32(0.0)
            return spec.apply(state, agg, ids, gval)

        if spec.halt is None:
            def fori(_, s):
                return one_iter(s)
            final = lax.fori_loop(0, max_iters, fori, state)
            return final, jnp.int32(max_iters)

        def cond(carry):
            _, i, done = carry
            return jnp.logical_and(i < max_iters, jnp.logical_not(done))

        def step(carry):
            s, i, _ = carry
            new = one_iter(s)
            return new, i + 1, spec.halt(s, new, valid)

        final, iters, _ = lax.while_loop(
            cond, step, (state, jnp.int32(0), jnp.array(False)))
        return final, iters

    key = ("fused", spec, max_iters, V, ell.nbr.shape, use_pallas,
           block_rows, init_state.shape, str(init_state.dtype))
    fn, key = _jit_cache_get(key)
    if fn is None:
        fn = jax.jit(body)
        _jit_cache_put(key, fn)
    return fn(ell.nbr, ell.mask, ell.w, init_state)


def run_pregel_frontier(
    spec: PregelSpec,
    ell,
    init_state: Array,
    max_iters: int,
    block_rows: int = 1024,
    init_active: Optional[Array] = None,
    profile: bool = False,
):
    """Run the vertex program with frontier compression.

    ``ell`` is the uncapped ``direction='out'`` layout: row ``u`` lists
    the destinations of u's out-edges, so scanning a block of frontier
    rows touches exactly the edges incident to active vertices.  A
    packed active-vertex list (static capacity, dynamic count) rides
    the ``lax.while_loop`` carry; each superstep runs an inner
    ``fori_loop`` whose trip count is ``ceil(count / block_rows)`` —
    per-superstep gather/scatter work is proportional to the *actual*
    frontier, not V.

    Exactness (the reason results are bit-identical to dense):

    * ``'monotone'`` — the aggregate is rebuilt each round from active
      sources only and folded into state by apply's own min/max.  A
      source unchanged since round t delivered the same message at
      round t and the fold made it permanent; re-delivering it is a
      no-op.  min/max are exact in any order, so trajectories (and
      therefore halt rounds) match dense exactly.
    * ``'delta'`` — the full sum aggregate is carried across rounds;
      round 1 scatters every message, later rounds scatter
      ``msg(new) - msg(old)`` for changed sources.  Exact when messages
      are integer-valued in their dtype (k-core's 0/1 aliveness).

    The apply/halt/global_value hooks run densely over the full state,
    so padding-free [V] semantics, iteration counts, and gval match the
    dense path element for element.

    ``init_active`` (monotone mode only) overrides the first frontier
    with an explicit ``bool [V]`` mask — the incremental-maintenance
    seam: a warm ``init_state`` taken from a previous fixpoint plus an
    ``init_active`` of the delta's touched vertices runs only the
    repair wavefront.  Exact under the same monotone invariant, because
    an old-fixpoint state already reflects every untouched source's
    message (the fold made it permanent last snapshot).  Ignored in
    delta mode, where round 1 must scatter the full sum regardless.

    ``profile=True`` additionally returns a ``[max_iters] int32`` array
    of per-round frontier occupancy (the packed count each executed
    superstep scattered; untaken rounds stay 0) as a third output —
    the observability counters.  The occupancy rides the while-loop
    carry, so the flag is part of the jit key: the untraced program is
    byte-for-byte the old one (zero cost when off), and the counts are
    a pure *recording* of values the loop already computes, so state
    trajectories and halt rounds are unchanged.
    """
    _check_superstep_spec(spec, "run_pregel_frontier")
    mode = spec.frontier_mode
    if mode not in ("monotone", "delta"):
        raise ValueError(f"run_pregel_frontier: spec declares no "
                         f"frontier_mode (got {mode!r})")
    if mode == "monotone" and spec.combine not in ("min", "max"):
        raise ValueError("frontier_mode='monotone' requires a min/max "
                         "combine")
    if mode == "delta" and spec.combine != "sum":
        raise ValueError("frontier_mode='delta' requires a 'sum' combine")
    V = ell.n_vertices
    K = ell.nbr.shape[1]
    if init_state.shape[0] != V:
        raise ValueError("run_pregel_frontier: state must be unpadded [V]")
    B = min(block_rows, max(V, 1))
    F = ((V + B - 1) // B) * B          # packed-frontier capacity
    trailing = init_state.shape[1:]
    delta = mode == "delta"
    seeded = init_active is not None and not delta

    def body(nbr, msk, w, state, *extra):
        ids = jnp.arange(V, dtype=jnp.int32)
        valid = ids < V
        probe = jax.eval_shape(
            spec.message,
            jax.ShapeDtypeStruct((1, 1) + trailing, state.dtype),
            jax.ShapeDtypeStruct((1, 1), w.dtype))
        agg_dtype = (jnp.dtype(spec.message_dtype)
                     if spec.message_dtype is not None else probe.dtype)
        agg_trailing = probe.shape[2:]
        fill = jnp.asarray(0 if delta else spec.identity, agg_dtype)
        scatter = {"sum": lambda a, i, v: a.at[i].add(v),
                   "min": lambda a, i, v: a.at[i].min(v),
                   "max": lambda a, i, v: a.at[i].max(v)}[spec.combine]

        def reduce_active(ch):
            while ch.ndim > 1:
                ch = jnp.any(ch, axis=-1)
            return ch

        def pack(act):
            idx = jnp.nonzero(act, size=F, fill_value=V)[0]
            return idx.astype(jnp.int32), jnp.sum(act.astype(jnp.int32))

        def scatter_frontier(acc, state, prev, frontier, count, first):
            n_blocks = (count + B - 1) // B

            def blk(j, acc):
                fb = lax.dynamic_slice(frontier, (j * B,), (B,))
                row = jnp.clip(fb, 0, V - 1)
                rn = nbr[row]                  # (B, K), sentinel V
                rm = msk[row] & (fb < V)[:, None]
                rw = w[row]
                src = jnp.broadcast_to(state[row][:, None],
                                       (B, K) + trailing)
                msgs = spec.message(src, rw)
                if delta:
                    prev_src = jnp.broadcast_to(prev[row][:, None],
                                                (B, K) + trailing)
                    pm = spec.message(prev_src, rw)
                    msgs = msgs - jnp.where(first, jnp.zeros_like(pm), pm)
                if spec.message_dtype is not None:
                    msgs = msgs.astype(spec.message_dtype)
                m = rm
                if msgs.ndim > m.ndim:
                    m = m.reshape(m.shape + (1,) * (msgs.ndim - m.ndim))
                msgs = jnp.where(m, msgs.astype(agg_dtype), fill)
                # padded/inactive slots aim at the sentinel row V
                dst_f = jnp.where(rm, rn, V).reshape(-1)
                mf = msgs.reshape((B * K,) + msgs.shape[2:])
                return scatter(acc, dst_f, mf)

            return lax.fori_loop(0, n_blocks, blk, acc)

        def one_superstep(s, agg):
            if spec.global_value is not None:
                g_src = agg if spec.global_over_agg else s
                gval = spec.global_value(g_src, ids, valid)
            else:
                gval = jnp.float32(0.0)
            return spec.apply(s, agg, ids, gval)

        def halt_of(s, new):
            if spec.halt is None:
                return jnp.array(False)
            return spec.halt(s, new, valid)

        if delta:
            act0 = jnp.ones((V,), bool)     # round 1 seeds the full sum
        elif seeded:
            act0 = extra[0]
        elif spec.frontier_init is not None:
            act0 = reduce_active(spec.frontier_init(state))
        else:
            act0 = reduce_active(
                state != jnp.asarray(spec.identity, state.dtype))
        fr0, cnt0 = pack(act0)

        def cond(carry):
            i, done = carry[-2], carry[-1]
            return jnp.logical_and(i < max_iters, jnp.logical_not(done))

        # Occupancy recording (profile mode) rides the carry *between*
        # the payload and the (i, done) tail, so ``cond``'s
        # carry[-2]/carry[-1] indexing and the payload unpack both hold
        # in either shape.
        occ0 = jnp.zeros((max_iters,), jnp.int32)

        if delta:
            acc0 = jnp.zeros((V + 1,) + agg_trailing, agg_dtype)

            def step(carry):
                if profile:
                    s, prev, acc, fr, cnt, first, occ, i, _ = carry
                else:
                    s, prev, acc, fr, cnt, first, i, _ = carry
                acc = scatter_frontier(acc, s, prev, fr, cnt, first)
                new = one_superstep(s, acc[:V])
                fr2, cnt2 = pack(reduce_active(new != s))
                tail = (i + 1, halt_of(s, new))
                if profile:
                    tail = (occ.at[i].set(cnt),) + tail
                return (new, s, acc, fr2, cnt2, jnp.array(False)) + tail

            carry0 = (state, state, acc0, fr0, cnt0, jnp.array(True))
        else:
            def step(carry):
                if profile:
                    s, fr, cnt, occ, i, _ = carry
                else:
                    s, fr, cnt, i, _ = carry
                acc0 = jnp.full((V + 1,) + agg_trailing, fill, agg_dtype)
                acc = scatter_frontier(acc0, s, None, fr, cnt,
                                       jnp.array(False))
                new = one_superstep(s, acc[:V])
                fr2, cnt2 = pack(reduce_active(new != s))
                tail = (i + 1, halt_of(s, new))
                if profile:
                    tail = (occ.at[i].set(cnt),) + tail
                return (new, fr2, cnt2) + tail

            carry0 = (state, fr0, cnt0)

        if profile:
            carry0 = carry0 + (occ0,)
        carry0 = carry0 + (jnp.int32(0), jnp.array(False))

        out = lax.while_loop(cond, step, carry0)
        if profile:
            return out[0], out[-2], out[-3]
        return out[0], out[-2]

    key = ("frontier", spec, max_iters, V, K, B,
           init_state.shape, str(init_state.dtype), seeded, profile)
    fn, key = _jit_cache_get(key)
    if fn is None:
        fn = jax.jit(body)
        _jit_cache_put(key, fn)
    args = (jnp.asarray(init_active, bool),) if seeded else ()
    return fn(ell.nbr, ell.mask, ell.w, init_state, *args)
