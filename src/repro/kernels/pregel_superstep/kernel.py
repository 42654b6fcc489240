"""Pallas TPU kernel: fused Pregel superstep over ELL edge blocks.

The dense superstep lowers to three XLA ops — gather src state along
edges, the edge program over an [E] message tensor, segment-combine to
destinations — each a separate HBM round trip over O(E) data.  This
kernel fuses all three into one pass over the fixed-width in-neighbor
matrix:

    agg[v] = reduce_k( op, mask[v,k] ? message(x[nbr[v,k]], w[v,k])
                                     : fill )

The [E] message tensor is never materialized: messages live only in
VMEM registers between the gather and the row-reduction.

TPU mapping
-----------
* Grid over row tiles of ``R`` destination vertices.  Each step streams
  a ``(R, K)`` tile of ``nbr``/``mask``/``w`` from HBM and keeps the
  whole gather source ``x`` VMEM-resident (the ops wrapper enforces a
  byte budget and falls back to the jnp reference beyond it).
* The gather ``x[nbr]`` is a ``jnp.take`` of a ``(R, K)`` index tile
  from the VMEM-resident vector.  The chip's compiler refuses it
  (Mosaic: "Only 2D gather is supported" — it lowers only a same-shape
  ``take_along_axis``), so this kernel runs in interpret mode only and
  is off the served path; ``LocalEngine`` keeps ``use_pallas`` False.
* ``message`` is inlined into the kernel body — it must be elementwise
  jnp code (the ``PregelSpec.elementwise_message`` contract), so it
  compiles to VPU ops over the gathered tile.
* The combine is a VPU row-reduction straight into the [R] output tile:
  no segment-sort, no scatter, no second kernel launch.
* With ``message_dtype`` set, messages are cast before the reduce — the
  mixed-precision channel.  The reduce and output then carry the
  reduced dtype, exactly as the dense path's combine does.

VMEM budget per step: R*K*(4+4+1) bytes for the tile + x bytes
(+ R*out_itemsize).  Default R=512, K<=1024, x<=16 MiB -> well under
the ~16 MB VMEM ceiling for typical K.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _superstep_kernel(nbr_ref, mask_ref, w_ref, x_ref, y_ref, *,
                      message, op: str, fill, message_dtype):
    nbr = nbr_ref[...]                       # (R, K) int32
    msk = mask_ref[...]                      # (R, K) stored int8
    w = w_ref[...]                           # (R, K)
    x = x_ref[...]                           # (Vx,) — VMEM resident
    vals = jnp.take(x, jnp.clip(nbr, 0, x.shape[0] - 1), axis=0)
    msgs = message(vals, w)
    if message_dtype is not None:
        msgs = msgs.astype(message_dtype)
    contrib = jnp.where(msk != 0, msgs, jnp.asarray(fill, msgs.dtype))
    red = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[op]
    y_ref[...] = red(contrib, axis=1).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "message", "op", "fill", "message_dtype", "out_dtype", "block_rows",
    "interpret"))
def superstep_pallas(nbr, mask, w, x, *, message, op: str, fill,
                     message_dtype=None, out_dtype=None,
                     block_rows: int = 512, interpret: bool = False):
    """Tiled pallas_call. Caller guarantees: V % block_rows == 0,
    K % 128 == 0 (ops.py pads), x is 1-D and fits VMEM, ``message`` is
    elementwise/shape-polymorphic with stable identity (module-level
    function — it keys this jit cache)."""
    V, K = nbr.shape
    grid = (V // block_rows,)
    out_dtype = x.dtype if out_dtype is None else out_dtype
    return pl.pallas_call(
        functools.partial(_superstep_kernel, message=message, op=op,
                          fill=fill, message_dtype=message_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, K), lambda i: (i, 0)),   # nbr tile
            pl.BlockSpec((block_rows, K), lambda i: (i, 0)),   # mask tile
            pl.BlockSpec((block_rows, K), lambda i: (i, 0)),   # w tile
            pl.BlockSpec(x.shape, lambda i: (0,)),             # x resident
        ],
        out_specs=pl.BlockSpec((block_rows,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((V,), out_dtype),
        interpret=interpret,
    )(nbr, mask.astype(jnp.int8), w, x)
