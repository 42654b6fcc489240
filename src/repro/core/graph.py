"""Device-ready graph representations.

The paper's platform moves graphs between a distributed dataflow engine
(Spark/GraphFrames) and an in-memory graph database (Neo4j).  On TPU every
representation must be fixed-shape, so we keep three formats:

* ``GraphCOO``  — destination-sorted edge list, padded with a sentinel
  vertex id ``V`` so ``jax.ops.segment_*`` with ``num_segments=V+1`` drops
  padding for free.  This is the *exact* format (no degree cap) and the
  unit of edge partitioning for the distributed engine.
* ``GraphCSR``  — ``indptr/indices``; the LocalEngine's native format
  (the Neo4j "index-free adjacency" analogue: pointer-chase becomes slice).
* ``GraphELL`` — per-vertex neighbor lists padded to a max degree ``K``.
  This is the paper's ``MaxAdjacentNodes`` cap (Table I) turned into the
  TPU-native layout: gather + masked row-reduce is exactly what the VPU
  wants, and skew becomes padding instead of stragglers.
* ``OrientedELL`` — degree-ordered orientation of an undirected graph:
  each edge {u, v} kept once, directed from the lower-(degree, id) rank
  endpoint to the higher, with per-vertex *sorted* out-neighbor rows.
  Out-degrees under this orientation are bounded by O(sqrt(E)) (hubs
  rank last, so they receive rather than emit), which makes neighborhood
  intersection — triangle counting — linear in memory instead of the
  O(V^2/32)-bit bitset formulation.  Unlike ``GraphELL`` this is *exact*:
  the row width is the achieved max out-degree, not a lossy cap.

All constructors take host-side ``np.ndarray`` edge lists (the ETL layer
works in numpy, like Scalding worked in Hadoop) and produce pytrees of
``jnp`` arrays.
"""
from __future__ import annotations

import dataclasses
import hashlib
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@dataclasses.dataclass(frozen=True, eq=False)
class GraphDelta:
    """One snapshot-to-snapshot edge edit, as applied by
    :meth:`GraphCOO.apply_delta`.

    ``added``/``removed`` are ``[n, 2]`` int64 host arrays of logical
    (src, dst) pairs — *before* symmetrization, so an undirected graph's
    delta records each touched undirected edge once.  ``touched`` is the
    sorted unique endpoint set of every changed edge: the seed frontier
    for incremental algorithm maintenance, and the planner's estimate of
    how much of the graph an incremental recompute must visit.
    """

    added: np.ndarray      # [n_added, 2] int64
    removed: np.ndarray    # [n_removed, 2] int64
    touched: np.ndarray    # [n_touched] int32, sorted unique endpoints

    @property
    def n_added(self) -> int:
        return int(self.added.shape[0])

    @property
    def n_removed(self) -> int:
        return int(self.removed.shape[0])

    @property
    def n_touched(self) -> int:
        return int(self.touched.shape[0])

    def nbytes(self) -> int:
        """Bytes a consumer must ingest to apply this delta — the
        planner's incremental-path transfer term."""
        return int(self.added.nbytes + self.removed.nbytes
                   + self.touched.nbytes)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GraphCOO:
    """Destination-sorted, padded COO edge list.

    Padding edges have ``src == dst == n_vertices`` (the sentinel row) and
    ``w == 0``.
    """

    src: Array          # [E_pad] int32
    dst: Array          # [E_pad] int32, sorted ascending
    w: Array            # [E_pad] float32 (1.0 for unweighted)
    n_vertices: int     # static
    n_edges: int        # true edge count (static)
    symmetric: bool = False   # built via symmetrize=True (static metadata;
                              # set it manually if the edge list is already
                              # symmetric by construction)

    # -- pytree protocol (scalars are static aux data) ---------------------
    def tree_flatten(self):
        return (self.src, self.dst, self.w), (
            self.n_vertices, self.n_edges, self.symmetric)

    @classmethod
    def tree_unflatten(cls, aux, children):
        src, dst, w = children
        return cls(src, dst, w, *aux)

    @property
    def e_pad(self) -> int:
        return int(self.src.shape[0])

    def nbytes(self) -> int:
        return self.e_pad * (4 + 4 + 4)

    def content_digest(self) -> str:
        """Content identity of this graph: a digest over the true (un-padded)
        edge buffers plus the structural metadata.  Two byte-identical
        graphs — e.g. the same snapshot reloaded — share one digest, and
        distinct graphs can never collide the way recycled ``id()``
        values can.  Computed once (one device->host transfer) and
        memoized on the instance; the memo is a plain attribute, not a
        pytree leaf, so tracing never sees it."""
        d = getattr(self, "_content_digest", None)
        if d is None:
            h = hashlib.blake2b(digest_size=16)
            h.update(f"{self.n_vertices}|{self.n_edges}|"
                     f"{self.symmetric}".encode())
            for buf in (self.src, self.dst, self.w):
                h.update(np.ascontiguousarray(
                    np.asarray(buf)[: self.n_edges]).tobytes())
            d = h.hexdigest()
            self._content_digest = d
        return d

    def apply_delta(
        self,
        added=None,
        removed=None,
        added_w: Optional[np.ndarray] = None,
        pad_multiple: int = 1024,
    ) -> "GraphCOO":
        """Edit the edge set without re-landing the snapshot: returns a
        new canonical :class:`GraphCOO` with recorded lineage.

        ``added``/``removed`` are iterables of logical (src, dst) pairs
        (anything ``np.asarray`` reshapes to ``[n, 2]``).  On a
        symmetric graph each logical pair stands for the undirected
        edge — both directions are edited.  Removals apply before
        additions, so remove+add of the same pair is a weight update;
        adding an edge that already exists is a no-op (the existing
        weight wins, matching ``build_coo``'s first-occurrence dedup).

        Because the result routes through ``build_coo``'s
        canonicalization (dedup + destination sort), its
        ``content_digest`` is **bit-identical** to building the edited
        edge list from scratch — lineage-equal graphs are cache-equal.
        The new graph carries ``parent_digest`` (this graph's digest)
        and ``delta`` (a :class:`GraphDelta`) as plain host attributes
        for the catalog's lineage chain and the planner's
        incremental-vs-full pricing.
        """
        def _pairs(edges) -> np.ndarray:
            if edges is None:
                return np.zeros((0, 2), dtype=np.int64)
            e = np.asarray(edges, dtype=np.int64)
            return e.reshape(-1, 2) if e.size else np.zeros((0, 2),
                                                            dtype=np.int64)

        add = _pairs(added)
        rem = _pairs(removed)
        V = self.n_vertices
        for name, e in (("added", add), ("removed", rem)):
            if e.size and (e.min() < 0 or e.max() >= V):
                raise ValueError(
                    f"apply_delta: {name} edge endpoints must lie in "
                    f"[0, {V}); got range [{e.min()}, {e.max()}]")
        touched = np.unique(
            np.concatenate([add.ravel(), rem.ravel()])).astype(np.int32)

        if added_w is None:
            add_w = np.ones(add.shape[0], dtype=np.float32)
        else:
            add_w = np.asarray(added_w, dtype=np.float32).reshape(-1)
            if add_w.shape[0] != add.shape[0]:
                raise ValueError("apply_delta: added_w length mismatch")
        add_s, add_d = add[:, 0], add[:, 1]
        rem_s, rem_d = rem[:, 0], rem[:, 1]
        if self.symmetric:
            add_s, add_d = (np.concatenate([add_s, add_d]),
                            np.concatenate([add_d, add_s]))
            add_w = np.concatenate([add_w, add_w])
            rem_s, rem_d = (np.concatenate([rem_s, rem_d]),
                            np.concatenate([rem_d, rem_s]))

        src = np.asarray(self.src)[: self.n_edges].astype(np.int64)
        dst = np.asarray(self.dst)[: self.n_edges].astype(np.int64)
        w = np.asarray(self.w)[: self.n_edges]
        stride = np.int64(V + 1)
        if rem_s.size:
            keep = ~np.isin(src * stride + dst, rem_s * stride + rem_d)
            src, dst, w = src[keep], dst[keep], w[keep]
        new = build_coo(
            np.concatenate([src, add_s]), np.concatenate([dst, add_d]), V,
            w=np.concatenate([w, add_w]), pad_multiple=pad_multiple,
            symmetrize=False, dedup=True)
        # symmetric is digest-header metadata: restore it before any
        # digest is computed so lineage-equal graphs stay cache-equal
        new.symmetric = self.symmetric
        new.parent_digest = self.content_digest()
        new.delta = GraphDelta(added=add, removed=rem, touched=touched)
        return new


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GraphCSR:
    """CSR adjacency: out-neighbors of v are indices[indptr[v]:indptr[v+1]]."""

    indptr: Array       # [V+1] int32
    indices: Array      # [E_pad] int32 (padded tail with sentinel V)
    w: Array            # [E_pad] float32
    n_vertices: int
    n_edges: int

    def tree_flatten(self):
        return (self.indptr, self.indices, self.w), (self.n_vertices, self.n_edges)

    @classmethod
    def tree_unflatten(cls, aux, children):
        indptr, indices, w = children
        return cls(indptr, indices, w, aux[0], aux[1])

    def nbytes(self) -> int:
        return int(self.indptr.shape[0]) * 4 + int(self.indices.shape[0]) * 8


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GraphELL:
    """ELLPACK: fixed-width neighbor matrix (the MaxAdjacentNodes layout).

    ``nbr[v, k]`` is the k-th in-neighbor of ``v`` (source of an edge into
    v); invalid slots have ``mask == False`` and ``nbr == n_vertices``
    (sentinel, so gathers read the identity pad row).
    """

    nbr: Array          # [V, K] int32
    mask: Array         # [V, K] bool
    w: Array            # [V, K] float32
    n_vertices: int
    n_edges: int        # edges retained after capping
    n_edges_total: int  # edges before capping (for Table I loss accounting)

    def tree_flatten(self):
        return (self.nbr, self.mask, self.w), (
            self.n_vertices, self.n_edges, self.n_edges_total)

    @classmethod
    def tree_unflatten(cls, aux, children):
        nbr, mask, w = children
        return cls(nbr, mask, w, aux[0], aux[1], aux[2])

    @property
    def max_degree(self) -> int:
        return int(self.nbr.shape[1])

    @property
    def lost_fraction(self) -> float:
        """Table I: fraction of edges dropped by the degree cap."""
        if self.n_edges_total == 0:
            return 0.0
        return 1.0 - self.n_edges / self.n_edges_total

    def nbytes(self) -> int:
        v, k = self.nbr.shape
        return int(v) * int(k) * (4 + 1 + 4)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class OrientedELL:
    """Degree-ordered orientation with sorted out-neighbor rows.

    Every undirected edge {u, v} appears exactly once as the oriented
    pair ``(eu[i], ev[i])`` where ``rank(u) < rank(v)`` under the
    lexicographic ``(degree, id)`` order (self-loops drop out — no
    vertex out-ranks itself).  ``nbr[v]`` holds v's oriented
    out-neighbors sorted ascending by id; invalid slots carry the
    sentinel ``n_vertices``, and one extra all-sentinel row at index
    ``n_vertices`` lets padded edge slots gather an empty row.

    The number of triangles is ``sum_i |nbr[eu[i]] ∩ nbr[ev[i]]|`` —
    each triangle counted exactly once, at its lowest-rank edge.
    """

    nbr: Array          # [V + 1, K] int32, rows sorted, sentinel-padded
    eu: Array           # [E_pad] int32 oriented edge tails (sentinel pad)
    ev: Array           # [E_pad] int32 oriented edge heads (sentinel pad)
    n_vertices: int
    n_edges: int        # true oriented (== undirected) edge count

    def tree_flatten(self):
        return (self.nbr, self.eu, self.ev), (self.n_vertices, self.n_edges)

    @classmethod
    def tree_unflatten(cls, aux, children):
        nbr, eu, ev = children
        return cls(nbr, eu, ev, aux[0], aux[1])

    @property
    def max_out_degree(self) -> int:
        return int(self.nbr.shape[1])

    def nbytes(self) -> int:
        return (int(self.nbr.shape[0]) * int(self.nbr.shape[1])
                + 2 * int(self.eu.shape[0])) * 4


# ---------------------------------------------------------------------------
# Host-side constructors (numpy; this is the ETL substrate's device handoff)
# ---------------------------------------------------------------------------

def _pad_to(x: np.ndarray, n: int, fill) -> np.ndarray:
    if x.shape[0] >= n:
        return x[:n]
    pad = np.full((n - x.shape[0],) + x.shape[1:], fill, dtype=x.dtype)
    return np.concatenate([x, pad], axis=0)


def round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def build_coo(
    src: np.ndarray,
    dst: np.ndarray,
    n_vertices: int,
    w: Optional[np.ndarray] = None,
    pad_multiple: int = 1024,
    symmetrize: bool = False,
    dedup: bool = True,
) -> GraphCOO:
    """Sort edges by destination, optionally symmetrize/dedup, pad.

    Duplicate (src, dst) pairs keep their first occurrence's weight;
    within a destination, edges are ordered by source."""
    src = np.asarray(src, dtype=np.int32)
    dst = np.asarray(dst, dtype=np.int32)
    if w is None:
        w = np.ones_like(src, dtype=np.float32)
    w = np.asarray(w, dtype=np.float32)
    if symmetrize:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
        w = np.concatenate([w, w])
    if dedup and src.size:
        # destination-major keys: unique's sorted order is the final edge
        # order, so no second sort is needed
        key = (dst.astype(np.int64) * np.int64(n_vertices + 1)
               + src.astype(np.int64))
        _, keep = np.unique(key, return_index=True)
        src, dst, w = src[keep], dst[keep], w[keep]
    else:
        order = np.argsort(dst, kind="stable")
        src, dst, w = src[order], dst[order], w[order]
    n_edges = int(src.shape[0])
    e_pad = max(pad_multiple, round_up(n_edges, pad_multiple))
    sentinel = np.int32(n_vertices)
    return GraphCOO(
        src=jnp.asarray(_pad_to(src, e_pad, sentinel)),
        dst=jnp.asarray(_pad_to(dst, e_pad, sentinel)),
        w=jnp.asarray(_pad_to(w, e_pad, 0.0)),
        n_vertices=int(n_vertices),
        n_edges=n_edges,
        symmetric=bool(symmetrize),
    )


def build_csr(
    src: np.ndarray,
    dst: np.ndarray,
    n_vertices: int,
    w: Optional[np.ndarray] = None,
    pad_multiple: int = 1024,
    symmetrize: bool = False,
) -> GraphCSR:
    """CSR over *out*-neighbors: row v lists targets of edges from v."""
    src = np.asarray(src, dtype=np.int32)
    dst = np.asarray(dst, dtype=np.int32)
    if w is None:
        w = np.ones_like(src, dtype=np.float32)
    w = np.asarray(w, dtype=np.float32)
    if symmetrize:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
        w = np.concatenate([w, w])
    order = np.argsort(src, kind="stable")
    src, dst, w = src[order], dst[order], w[order]
    counts = np.bincount(src, minlength=n_vertices).astype(np.int32)
    indptr = np.zeros(n_vertices + 1, dtype=np.int32)
    np.cumsum(counts, out=indptr[1:])
    n_edges = int(src.shape[0])
    e_pad = max(pad_multiple, round_up(n_edges, pad_multiple))
    return GraphCSR(
        indptr=jnp.asarray(indptr),
        indices=jnp.asarray(_pad_to(dst, e_pad, np.int32(n_vertices))),
        w=jnp.asarray(_pad_to(w, e_pad, 0.0)),
        n_vertices=int(n_vertices),
        n_edges=n_edges,
    )


def build_ell(
    src: np.ndarray,
    dst: np.ndarray,
    n_vertices: int,
    max_degree: int,
    w: Optional[np.ndarray] = None,
    symmetrize: bool = False,
    direction: str = "in",
) -> GraphELL:
    """Pack edges into the fixed-width ELL layout, capping per-vertex degree.

    ``direction='in'``: row v holds *sources* of edges into v (what SpMV /
    message aggregation wants).  Edges beyond ``max_degree`` for a vertex
    are dropped — this is exactly the paper's ``MaxAdjacentNodes``
    restriction, and ``lost_fraction`` reproduces Table I.
    """
    src = np.asarray(src, dtype=np.int32)
    dst = np.asarray(dst, dtype=np.int32)
    if w is None:
        w = np.ones_like(src, dtype=np.float32)
    w = np.asarray(w, dtype=np.float32)
    if symmetrize:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
        w = np.concatenate([w, w])
    if direction == "in":
        row, col = dst, src
    else:
        row, col = src, dst
    n_total = int(row.shape[0])
    order = np.argsort(row, kind="stable")
    row, col, w = row[order], col[order], w[order]
    counts = np.bincount(row, minlength=n_vertices)
    # slot index of each edge within its row
    starts = np.zeros(n_vertices, dtype=np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    slot = np.arange(n_total, dtype=np.int64) - starts[row]
    keep = slot < max_degree
    row_k, col_k, w_k, slot_k = row[keep], col[keep], w[keep], slot[keep]
    nbr = np.full((n_vertices, max_degree), np.int32(n_vertices), dtype=np.int32)
    mask = np.zeros((n_vertices, max_degree), dtype=bool)
    wm = np.zeros((n_vertices, max_degree), dtype=np.float32)
    nbr[row_k, slot_k] = col_k
    mask[row_k, slot_k] = True
    wm[row_k, slot_k] = w_k
    return GraphELL(
        nbr=jnp.asarray(nbr),
        mask=jnp.asarray(mask),
        w=jnp.asarray(wm),
        n_vertices=int(n_vertices),
        n_edges=int(keep.sum()),
        n_edges_total=n_total,
    )


def build_oriented_ell(
    src: np.ndarray,
    dst: np.ndarray,
    n_vertices: int,
    pad_multiple: int = 1024,
) -> OrientedELL:
    """Degree-order and orient a symmetrized, deduped edge list.

    Input must contain both directions of every undirected edge (the
    ``build_coo(..., symmetrize=True)`` invariant); exactly one survives
    orientation.  Self-loops and sentinel padding rows are dropped.  The
    achieved row width is the orientation's max out-degree — O(sqrt(E))
    even on heavy-tailed graphs, because high-degree hubs rank last and
    therefore *receive* nearly all their edges.
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    real = (src < n_vertices) & (dst < n_vertices) & (src != dst)
    src, dst = src[real], dst[real]
    deg = np.bincount(dst, minlength=n_vertices)
    # keep (u, v) iff (deg[u], u) < (deg[v], v) — the degree-ordered
    # orientation; ties broken by id so every edge survives exactly once
    keep = (deg[src] < deg[dst]) | ((deg[src] == deg[dst]) & (src < dst))
    eu, ev = src[keep], dst[keep]
    order = np.lexsort((ev, eu))          # rows grouped, sorted by head id
    eu, ev = eu[order], ev[order]
    n_edges = int(eu.shape[0])
    counts = np.bincount(eu, minlength=n_vertices)
    k = max(int(counts.max()) if n_edges else 1, 1)
    starts = np.zeros(n_vertices, dtype=np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    slot = np.arange(n_edges, dtype=np.int64) - starts[eu]
    sentinel = np.int32(n_vertices)
    nbr = np.full((n_vertices + 1, k), sentinel, dtype=np.int32)
    nbr[eu, slot] = ev.astype(np.int32)
    e_pad = max(pad_multiple, round_up(max(n_edges, 1), pad_multiple))
    return OrientedELL(
        nbr=jnp.asarray(nbr),
        eu=jnp.asarray(_pad_to(eu.astype(np.int32), e_pad, sentinel)),
        ev=jnp.asarray(_pad_to(ev.astype(np.int32), e_pad, sentinel)),
        n_vertices=int(n_vertices),
        n_edges=n_edges,
    )


# ---------------------------------------------------------------------------
# Device-side primitives shared by engines
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_vertices", "op"))
def segment_combine(values: Array, segment_ids: Array, n_vertices: int, op: str):
    """Aggregate edge messages to destination vertices.

    ``segment_ids`` may contain the sentinel ``n_vertices`` (padding); one
    extra segment swallows it and is dropped.  ``op`` in {sum,min,max}.
    """
    n = n_vertices + 1
    if op == "sum":
        out = jax.ops.segment_sum(values, segment_ids, num_segments=n)
    elif op == "min":
        out = jax.ops.segment_min(values, segment_ids, num_segments=n)
    elif op == "max":
        out = jax.ops.segment_max(values, segment_ids, num_segments=n)
    else:
        raise ValueError(f"unknown op {op!r}")
    return out[:n_vertices]


def pad_vertex_state(x: Array, identity) -> Array:
    """Append the sentinel row so gathers through padded ids read identity."""
    pad = jnp.full((1,) + x.shape[1:], identity, dtype=x.dtype)
    return jnp.concatenate([x, pad], axis=0)


def require_symmetric(g: GraphCOO, algorithm: str) -> None:
    """Guard for algorithms with undirected semantics — on a directed
    edge list they run fine but return silently wrong answers."""
    if not getattr(g, "symmetric", False):
        raise ValueError(
            f"{algorithm} has undirected semantics and needs a symmetrized "
            f"edge list: build with build_coo(..., symmetrize=True), or set "
            f"coo.symmetric = True if the edges are already symmetric by "
            f"construction")


def out_degrees(g: GraphCOO) -> Array:
    ones = (g.src < g.n_vertices).astype(jnp.float32)
    return segment_combine(ones, g.src, g.n_vertices, "sum")


def in_degrees(g: GraphCOO) -> Array:
    ones = (g.dst < g.n_vertices).astype(jnp.float32)
    return segment_combine(ones, g.dst, g.n_vertices, "sum")
