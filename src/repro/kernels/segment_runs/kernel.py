"""Pallas TPU kernel: segmented inclusive scan along destination runs.

The dense superstep's edge slots are sorted by destination, so each
vertex's messages lie in one contiguous run of slots.  This kernel scans
every run in one pass over the slots, restarting at each run boundary:

    out[i] = op( msgs[j] for j in run(i), j <= i )

so a run's last slot holds the run's whole reduction.  The ops wrapper
reads those ends (one V-long gather) in place of an E-long scatter.

TPU mapping
-----------
* Slots are laid out ``(rows, 128)``, row-major, so a run continues from
  lane 127 of one row to lane 0 of the next.  The grid walks the
  ``R``-row tiles in order ("arbitrary": a tile reads the carry its
  predecessor left).
* Within a tile, three log-step (Hillis-Steele) scans on the VPU, with
  ``pltpu.roll`` shifting lanes and sublanes on the XLU:
    1. along each row's 128 lanes; a step adds the slot ``k`` lanes back
       when it holds the same destination (sorted slots: equal ends mean
       the whole stretch between is one run);
    2. along the tile's rows, over each row's last slot, which gives the
       run open at each row's end its partial from the tile's start;
    3. the carry from the previous tile (its last slot's destination and
       partial) joins the rows whose open run began before the tile; then
       every slot whose run began in an earlier row takes that row's
       end partial.
* Nothing is gathered in the kernel, so Mosaic's 1-D gather refusal does
  not apply; runs of any length (a hub's tens of thousands of slots) are
  carried across rows and tiles.
* Sums accumulate in the message's own (32-bit) dtype, in log-step tree
  order within a row and sequentially across rows and tiles; min and max
  are exact in any order.

VMEM per step: the ``(R, 128)`` destination, message and output tiles,
double-buffered, plus the scan's temporaries: about 20 * R * 128 * 4
bytes, 5 MiB at R = 512.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128

_COMBINE = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def _scan(v, d, pos, size: int, axis: int, f):
    """Segmented inclusive scan of ``v`` along ``axis`` (``size`` long),
    a step joining positions whose destinations ``d`` are equal."""
    k = 1
    while k < size:
        same = (pos >= k) & (pltpu.roll(d, k, axis) == d)
        v = jnp.where(same, f(v, pltpu.roll(v, k, axis)), v)
        k *= 2
    return v


def _runs_scan_kernel(d_ref, m_ref, o_ref, carry_v, carry_d, *, op: str,
                      rows: int):
    f = _COMBINE[op]

    @pl.when(pl.program_id(0) == 0)
    def _():                          # the first tile: no run is open
        carry_d[...] = jnp.full(carry_d.shape, -1, carry_d.dtype)
        carry_v[...] = jnp.zeros(carry_v.shape, carry_v.dtype)

    d = d_ref[...]                                        # (R, 128) int32
    v = m_ref[...]                                        # (R, 128)
    shape = (rows, LANES)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    row = lax.broadcasted_iota(jnp.int32, shape, 0)
    # 1. within each row
    v = _scan(v, d, lane, LANES, 1, f)
    # 2. the run open at each row's end, from the tile's start
    end_d = jnp.broadcast_to(d[:, LANES - 1:], shape)
    end_v = _scan(jnp.broadcast_to(v[:, LANES - 1:], shape), end_d, row,
                  rows, 0, f)
    # 3. from the tile's start back into the previous tile
    cd = jnp.broadcast_to(carry_d[...], shape)
    end_v = jnp.where(end_d == cd,
                      f(end_v, jnp.broadcast_to(carry_v[...], shape)), end_v)
    prev_d = jnp.where(row == 0, cd, pltpu.roll(end_d, 1, 0))
    prev_v = jnp.where(row == 0, jnp.broadcast_to(carry_v[...], shape),
                       pltpu.roll(end_v, 1, 0))
    o_ref[...] = jnp.where(d == prev_d, f(v, prev_v), v)
    carry_d[...] = end_d[rows - 1:, :]
    carry_v[...] = end_v[rows - 1:, :]


@functools.partial(jax.jit, static_argnames=("op", "block_rows",
                                             "interpret"))
def runs_scan_pallas(dst, msgs, *, op: str, block_rows: int = 512,
                     interpret: bool = False):
    """``dst`` ``(N, 128)`` int32, non-decreasing in row-major order;
    ``msgs`` ``(N, 128)`` of a 32-bit dtype.  Returns the segmented
    inclusive scan, ``(N, 128)``.  ``block_rows`` is a
    multiple of 8 or ``N``.  A last tile that overhangs ``N`` reads
    padding after every real slot; the scan only carries forward, so the
    real slots never see it.  The output takes the message buffer's
    place (aliased)."""
    n = msgs.shape[0]
    r = block_rows
    tile = pl.BlockSpec((r, LANES), lambda j: (j, 0))
    return pl.pallas_call(
        functools.partial(_runs_scan_kernel, op=op, rows=r),
        grid=(pl.cdiv(n, r),),
        in_specs=[tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(msgs.shape, msgs.dtype),
        scratch_shapes=[pltpu.VMEM((1, LANES), msgs.dtype),
                        pltpu.VMEM((1, LANES), jnp.int32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(dst, msgs)
