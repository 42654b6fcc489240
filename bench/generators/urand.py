"""GAP ``urand``: a uniform random (Erdos-Renyi) graph (Beamer, Asanovic,
Patterson, arXiv:1508.03619): ``edge_factor * 2**scale`` edges whose two
endpoints are drawn uniformly from the ``2**scale`` vertices."""
from __future__ import annotations

import jax
import jax.numpy as jnp

PARAMS = ("scale", "edge_factor")


def raw_edges(key, *, scale: int, edge_factor: int):
    n = 1 << scale
    m = edge_factor * n
    k_src, k_dst = jax.random.split(key)
    src = jax.random.randint(k_src, (m,), 0, n, dtype=jnp.int32)
    dst = jax.random.randint(k_dst, (m,), 0, n, dtype=jnp.int32)
    return src, dst
