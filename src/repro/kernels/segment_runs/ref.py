"""The scatter the runs combine replaces: its oracle, and what
``segment_runs`` lowers to off a TPU."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_SEG = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
        "max": jax.ops.segment_max}


def segment_runs_ref(msgs, dst, offsets, op: str):
    """``jax.ops.segment_{op}`` of ``msgs`` by ``dst`` over the
    ``len(offsets) - 1`` vertices; slots at or past ``offsets[-1]`` (the
    padding) are dropped."""
    n_seg = offsets.shape[0] - 1
    seg = jnp.where(jnp.arange(dst.shape[0]) < offsets[-1], dst, n_seg)
    return _SEG[op](msgs, seg, num_segments=n_seg + 1)[:n_seg]
