"""Pallas TPU kernel: sorted-neighbor-row intersection counting.

The hot loop of degree-ordered triangle counting: for each oriented edge
``(u, v)`` the count is

    c[e] = | nbr[u] ∩ nbr[v] |

over the two *sorted, deduped* out-neighbor rows gathered for that edge.
Summed over all oriented edges this is exactly the triangle count (each
triangle surfaces once, at its lowest-rank edge).

TPU mapping
-----------
* The ops wrapper gathers the two ``(E, K)`` row tiles per edge chunk up
  front (an XLA HBM gather) and hands them over *transposed*, as
  ``(K, E)``: edges on the 128 lanes, row slots on the sublanes.
* Grid over edge tiles of ``R`` edges (a multiple of 128).  Each step
  streams a ``(K, R)`` tile of each side into VMEM.
* Per tile the intersection is a ``fori_loop`` over the K slots of
  ``b``: slot ``j`` is the sublane row ``bT[j, :]`` (a dynamic sublane
  load), compared against the whole ``aT`` tile (a sublane broadcast)
  and summed over the sublanes into the ``(1, R)`` accumulator.  Rows
  are deduped, so each match contributes exactly once; sortedness is
  what lets the jnp reference use a true ``searchsorted`` merge.
* Sentinel slots (``>= sentinel``) never match: ``b``'s sentinel slots
  are masked explicitly, and a sentinel in ``a`` can only equal a masked
  ``b`` value.  All-sentinel (padding-edge) rows therefore count 0.

The row-major layout (``(R, K)`` tiles, column ``b[:, j]`` sliced per
step) does not compile for the chip: Mosaic has no lowering for a
dynamic lane slice.  Here the dynamic index is on the sublane axis.

VMEM budget per step: 2 * K * R * 4 bytes of rows (double-buffered by
the pipeline) + R * 4 out.  R=256, K<=2048 -> 4 MiB of rows per buffer
(ops.py enforces the K bound and pads K to the sublane multiple).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _intersect_kernel(at_ref, bt_ref, y_ref, *, sentinel: int,
                      k_valid: int):
    a = at_ref[...]                       # (K, R) int32, columns sorted

    def body(j, acc):
        bj = bt_ref[pl.ds(j, 1), :]       # (1, R): slot j of every b row
        hit = jnp.logical_and(a == bj, bj != sentinel)
        return acc + jnp.sum(hit.astype(jnp.int32), axis=0, keepdims=True)

    acc = jnp.zeros((1, a.shape[1]), jnp.int32)
    # only the first k_valid slots of b can hold real ids; the sublane
    # padding beyond is all-sentinel and would contribute zero anyway
    y_ref[...] = lax.fori_loop(0, k_valid, body, acc)


@functools.partial(jax.jit, static_argnames=("sentinel", "k_valid",
                                             "block_edges", "interpret"))
def ell_intersect_pallas(at, bt, *, sentinel: int, k_valid: int,
                         block_edges: int = 256, interpret: bool = False):
    """Tiled pallas_call over transposed ``(K, E)`` row tiles; returns
    ``(1, E)`` counts.  Caller guarantees: E % block_edges == 0,
    block_edges % 128 == 0, K % 8 == 0 (ops.py pads), rows
    sorted/deduped/sentinel-padded."""
    k, e = at.shape
    grid = (e // block_edges,)
    return pl.pallas_call(
        functools.partial(_intersect_kernel, sentinel=sentinel,
                          k_valid=k_valid),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, block_edges), lambda i: (0, i)),   # aT tile
            pl.BlockSpec((k, block_edges), lambda i: (0, i)),   # bT tile
        ],
        out_specs=pl.BlockSpec((1, block_edges), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, e), jnp.int32),
        interpret=interpret,
    )(at, bt)
