"""Pure-jnp oracle for the fused Pregel superstep kernel.

One superstep over the in-neighbor ELL layout, under the exact
signature the Pallas kernel implements:

    agg[v] = reduce_k( op, mask[v,k] ? message(x[nbr[v,k]], w[v,k])
                                     : fill )

where ``fill`` is the monoid identity for min/max and 0 for sum —
matching the dense path's segment-combine semantics (segment_sum drops
padded edges outright, so vertices with no message aggregate to 0
regardless of the declared identity; segment_min/max empties are
normalized to the identity).

Unlike ``ell_combine`` this takes the *edge program* as a parameter:
``message`` must be elementwise in ``(src_state, w)`` and
shape-polymorphic (it is called on ``[V, K]`` gathered tiles here and
on ``[E]`` edge vectors by the dense path — the ``PregelSpec.
elementwise_message`` contract).  Trailing state dims are supported
(messages ``[V, K, ...]`` reduce over axis 1), which is how fused-batch
(``batched_spec``) programs ride the same kernel signature.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _fill_value(op: str, identity):
    return 0 if op == "sum" else identity


@partial(jax.jit, static_argnames=("message", "op", "identity",
                                   "message_dtype"))
def superstep_ref(nbr, mask, w, x, *, message, op: str, identity,
                  message_dtype=None):
    """agg[v] = reduce_k over masked message(x[nbr[v,k]], w[v,k]).

    nbr : [V, K] int32 (sentinel/invalid slots guarded by mask)
    x   : [Vx] or [Vx, ...] gather source (vertex state)
    Returns [V] or [V, ...] aggregates in the message dtype (cast to
    ``message_dtype`` first when set — the reduced-precision channel).
    """
    with jax.named_scope("pregel.gather"):
        vals = jnp.take(x, jnp.clip(nbr, 0, x.shape[0] - 1), axis=0)
    msgs = message(vals, w)
    if message_dtype is not None:
        msgs = msgs.astype(message_dtype)
    m = mask != 0
    if msgs.ndim > m.ndim:
        m = m.reshape(m.shape + (1,) * (msgs.ndim - m.ndim))
    fill = jnp.asarray(_fill_value(op, identity), msgs.dtype)
    contrib = jnp.where(m, msgs, fill)
    red = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[op]
    return red(contrib, axis=1)
