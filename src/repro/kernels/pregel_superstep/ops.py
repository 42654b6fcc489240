"""Public wrapper for the fused superstep kernel.

Handles padding to TPU tile alignment (rows -> block multiple, K -> 128
lanes), routes to interpret mode on CPU hosts, and falls back to the
pure-jnp reference — which is itself fused at the XLA level (one
gather+reduce, no [E] tensor) — whenever the Pallas kernel's
preconditions don't hold:

  * the gather source exceeds the VMEM byte budget,
  * vertex state has trailing dims (fused-batch [V, B] programs),
  * the edge program is not shape-polymorphic on a probe tile.

Both paths share one signature so engines flip implementations freely.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.pregel_superstep.kernel import superstep_pallas
from repro.kernels.pregel_superstep.ref import superstep_ref, _fill_value

_LANE = 128
# Bytes of gather source (vertex state) the kernel keeps VMEM-resident.
VMEM_X_BUDGET_BYTES = 16 * 1024 * 1024


def _on_cpu() -> bool:
    return jax.devices()[0].platform == "cpu"


def _round_up(n, m):
    return ((n + m - 1) // m) * m


def _probe(message, x, w):
    """Shape/dtype of the edge program on a (1, 1) tile — checks the
    elementwise contract and determines the message dtype without
    running anything."""
    try:
        return jax.eval_shape(
            message,
            jax.ShapeDtypeStruct((1, 1) + x.shape[1:], x.dtype),
            jax.ShapeDtypeStruct((1, 1), w.dtype))
    except (TypeError, ValueError):     # not shape-polymorphic: reference
        return None


def fused_superstep(nbr, mask, w, x, *, message, op: str, identity,
                    message_dtype=None, use_pallas: bool = True,
                    block_rows: int = 512, interpret=None):
    """One fused superstep: agg over masked message(x[nbr], w).

    Pallas path for 1-D state within the VMEM budget; jnp reference
    otherwise.  Bit-identical between the two for min/max monoids (and
    for integer-valued sums) — the property the frontier/fused variants
    contract relies on.
    """
    V, K = nbr.shape
    probe = _probe(message, x, w)
    pallas_ok = (
        use_pallas
        and x.ndim == 1
        and probe is not None
        and probe.shape == (1, 1)
        and x.size * x.dtype.itemsize <= VMEM_X_BUDGET_BYTES
    )
    if not pallas_ok:
        return superstep_ref(nbr, mask, w, x, message=message, op=op,
                             identity=identity,
                             message_dtype=message_dtype)
    out_dtype = message_dtype if message_dtype is not None else probe.dtype
    vp = _round_up(max(V, block_rows), block_rows)
    kp = _round_up(K, _LANE)
    if (vp, kp) != (V, K):
        nbr = jnp.pad(nbr, ((0, vp - V), (0, kp - K)))
        mask = jnp.pad(mask, ((0, vp - V), (0, kp - K)))
        w = jnp.pad(w, ((0, vp - V), (0, kp - K)))
    y = superstep_pallas(
        nbr, mask, w, x, message=message, op=op,
        fill=_fill_value(op, identity), message_dtype=message_dtype,
        out_dtype=jnp.dtype(out_dtype).name, block_rows=block_rows,
        interpret=_on_cpu() if interpret is None else interpret)
    return y[:V]


def fused_superstep_ref(nbr, mask, w, x, *, message, op: str, identity,
                        message_dtype=None, **_):
    """Reference path under the kernel signature."""
    return superstep_ref(nbr, mask, w, x, message=message, op=op,
                         identity=identity, message_dtype=message_dtype)
