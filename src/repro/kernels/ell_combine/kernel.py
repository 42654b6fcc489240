"""Pallas TPU kernel: ELL gather + monoid combine.

This is the hot loop of both flagship paper workloads — one PageRank
power iteration and one hash-to-min CC round are exactly

    y[v] = reduce_k( op, mask[v,k] ? f(w[v,k], x[nbr[v,k]]) : identity )

over the fixed-width (MaxAdjacentNodes) neighbor matrix.

TPU mapping
-----------
* Grid over row tiles of ``R`` vertices.  Each step loads a ``(R, K)``
  tile of ``nbr``/``mask``/``w`` into VMEM and keeps the *whole* gather
  source ``x`` VMEM-resident (vertex states are O(V) floats; for the
  sharded engine V is the per-shard vertex range, which fits VMEM for
  v_local <= ~1M — the ops wrapper enforces the budget).
* The gather ``x[nbr]`` is a ``jnp.take`` of a ``(R, K)`` index tile
  from the VMEM-resident vector.  The chip's compiler refuses it
  (Mosaic: "Only 2D gather is supported" — it lowers only a same-shape
  ``take_along_axis``), so this kernel runs in interpret mode only and
  no engine calls it.
* The reduce is a VPU row-reduction; no MXU involvement (SpMV is
  bandwidth-bound, the roofline term we optimize is HBM streaming of the
  (R, K) tiles, which this layout makes perfectly sequential).

VMEM budget per step: R*K*(4+4+1) bytes for the tile + 4*Vx for x
(+ R*4 out).  Default R=512, K<=1024, Vx<=1M -> ~8.6 MB < 16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_IDENTITY = {"sum": 0.0, "min": float("inf"), "max": float("-inf")}


def _ell_kernel(nbr_ref, mask_ref, w_ref, x_ref, y_ref, *, op: str):
    nbr = nbr_ref[...]                       # (R, K) int32
    msk = mask_ref[...]                      # (R, K) bool (stored int8)
    x = x_ref[...]                           # (Vx,) f32 — VMEM resident
    vals = jnp.take(x, jnp.clip(nbr, 0, x.shape[0] - 1), axis=0)
    if op == "sum":
        w = w_ref[...]
        contrib = jnp.where(msk != 0, vals * w, 0.0)
        y_ref[...] = jnp.sum(contrib, axis=1)
    else:
        ident = jnp.asarray(_IDENTITY[op], vals.dtype)
        contrib = jnp.where(msk != 0, vals, ident)
        red = jnp.min if op == "min" else jnp.max
        y_ref[...] = red(contrib, axis=1)


@functools.partial(jax.jit, static_argnames=("op", "block_rows", "interpret"))
def ell_combine_pallas(nbr, mask, w, x, *, op: str = "sum",
                       block_rows: int = 512, interpret: bool = False):
    """Tiled pallas_call. Caller guarantees:
    V % block_rows == 0, K % 128 == 0 (ops.py pads), x fits VMEM."""
    V, K = nbr.shape
    grid = (V // block_rows,)
    return pl.pallas_call(
        functools.partial(_ell_kernel, op=op),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, K), lambda i: (i, 0)),   # nbr tile
            pl.BlockSpec((block_rows, K), lambda i: (i, 0)),   # mask tile
            pl.BlockSpec((block_rows, K), lambda i: (i, 0)),   # w tile
            pl.BlockSpec(x.shape, lambda i: (0,)),             # x resident
        ],
        out_specs=pl.BlockSpec((block_rows,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((V,), x.dtype),
        interpret=interpret,
    )(nbr, mask.astype(jnp.int8), w, x)
