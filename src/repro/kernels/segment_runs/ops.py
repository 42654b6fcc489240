"""Public wrapper: segment-reduce destination-sorted messages by runs.

``segment_runs(msgs, dst, offsets, op)`` is ``jax.ops.segment_{op}`` of
``[E]`` messages by ``dst`` over ``V = len(offsets) - 1`` segments, for
slots sorted by destination: vertex ``v``'s messages are slots
``offsets[v]:offsets[v + 1]``.  On a TPU it scans the runs with the
Pallas kernel and reads each run's last slot, with no E-long scatter
(``segment_runs_pallas``); on any other backend it is the scatter
(``segment_runs_ref``).  The backend is chosen where the program is
lowered (``lax.platform_dependent``), so a TPU program built on a CPU
host gets the kernel.  Empty segments hold the op's neutral element, as
``jax.ops.segment_*`` leaves them: 0, or the dtype's largest (min) or
smallest (max) value.

The end read gathers the 128-slot row that holds each run's end and
picks the end's lane (whole rows gather at about twice the rate of
single slots on a v5e), in chunks of ``READ_CHUNK`` vertices, so the
gathered rows hold at most ``READ_CHUNK * 128`` slots (32 MiB).

Dtypes narrower than 32 bits are widened for the scan and the result is
cast back, so a sum of bfloat16 messages accumulates in float32; uint32
is scanned as int32 bits (see ``_encode``).
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
from jax import lax

from repro.kernels.segment_runs.kernel import LANES, runs_scan_pallas
from repro.kernels.segment_runs.ref import segment_runs_ref

BLOCK_ROWS = 512
READ_CHUNK = 1 << 16


def _encode(m, op: str):
    """Messages as the kernel scans them: 32 bits wide, and signed where
    it compares (Mosaic has no unsigned min/max).  uint32 takes int32 by
    its bits, offset by 2**31 for min/max so that the order holds."""
    if m.dtype == jnp.uint32:
        if op != "sum":
            m = m ^ jnp.uint32(1 << 31)
        return lax.bitcast_convert_type(m, jnp.int32)
    if m.dtype.itemsize == 4:
        return m
    if jnp.issubdtype(m.dtype, jnp.floating):
        return m.astype(jnp.float32)
    return m.astype(jnp.int32)


def _decode(got, op: str, dtype):
    """The inverse of ``_encode`` (narrow dtypes: one final rounding)."""
    if dtype == jnp.uint32:
        got = lax.bitcast_convert_type(got, jnp.uint32)
        return got if op == "sum" else got ^ jnp.uint32(1 << 31)
    return got.astype(dtype)


def _neutral(op: str, dtype):
    """The value ``jax.ops.segment_{op}`` leaves in an empty segment."""
    dtype = jnp.dtype(dtype)
    if op == "sum":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if op == "min" else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if op == "min" else info.min, dtype)


def _read_ends(scanned, ends):
    """``scanned.ravel()[ends]`` of an ``(N, 128)`` scan, ``ends`` ``[V]``
    in bounds: whole rows gathered, the end's lane kept by a max over a
    select (exact: one lane is kept, the rest are the lowest value)."""
    n = ends.shape[0]
    chunk = max(1, min(READ_CHUNK, n))
    ends = jnp.pad(ends, (0, -n % chunk))
    low = _neutral("max", scanned.dtype)

    def read(e):
        got = scanned[e // LANES]                         # [c, 128]
        lane = lax.broadcasted_iota(jnp.int32, got.shape, 1)
        return jnp.max(jnp.where(lane == (e % LANES)[:, None], got, low),
                       axis=1)
    return lax.map(read, ends.reshape(-1, chunk)).reshape(-1)[:n]


def segment_runs_pallas(msgs, dst, offsets, op: str, *,
                        interpret: bool = False):
    """The kernel path of ``segment_runs`` (see there)."""
    e = dst.shape[0]
    m = _encode(msgs, op)
    pad = -e % LANES
    d = dst
    if pad:   # whole rows: the tail takes a destination past every real one
        d = jnp.concatenate(
            [dst, jnp.full((pad,), jnp.iinfo(jnp.int32).max, jnp.int32)])
        m = jnp.pad(m, (0, pad))
    rows = (e + pad) // LANES
    scanned = runs_scan_pallas(d.reshape(rows, LANES),
                               m.reshape(rows, LANES), op=op,
                               block_rows=min(BLOCK_ROWS, rows),
                               interpret=interpret)
    got = _read_ends(scanned, jnp.maximum(offsets[1:] - 1, 0))
    got = _decode(got, op, msgs.dtype)
    return jnp.where(offsets[1:] > offsets[:-1], got,
                     _neutral(op, msgs.dtype))


def segment_runs(msgs, dst, offsets, op: str):
    """``msgs`` ``[E]``, ``dst`` ``[E]`` non-decreasing int32, ``offsets``
    ``[V + 1]`` int32 with ``dst[offsets[v]:offsets[v+1]]`` all ``v``.
    Slots past ``offsets[V]`` (the padding) are not read.  Returns
    ``[V]`` in ``msgs``' dtype."""
    return lax.platform_dependent(
        msgs, dst, offsets,
        tpu=functools.partial(segment_runs_pallas, op=op),
        default=functools.partial(segment_runs_ref, op=op))
