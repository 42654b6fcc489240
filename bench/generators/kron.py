"""GAP ``kron``: the Graph500 Kronecker generator (Beamer, Asanovic,
Patterson, arXiv:1508.03619; Graph500 specification, section 3).

``edge_factor * 2**scale`` edges, each placed by ``scale`` independent
quadrant choices with probabilities A, B, C and D = 1 - A - B - C.  Vertex
ids are then relabelled, as Graph500 and GAP do, so that the hubs do not
sit at the lowest ids: here by a seeded bijection of ``[0, 2**scale)``
(two rounds of odd multiply and xor-shift) in place of a shuffled
permutation, whose sort takes the TPU compiler half a minute at this size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

PARAMS = ("scale", "edge_factor", "a", "b", "c")


def raw_edges(key, *, scale: int, edge_factor: int, a: float, b: float,
              c: float):
    n = 1 << scale
    m = edge_factor * n
    k_bits, k_perm = jax.random.split(key)

    def level(i, carry):
        src, dst = carry
        r = jax.random.uniform(jax.random.fold_in(k_bits, i), (m,))
        src_bit = r >= a + b                           # quadrants C, D
        dst_bit = ((r >= a) & (r < a + b)) | (r >= a + b + c)  # B, D
        return (src | (src_bit.astype(jnp.int32) << i),
                dst | (dst_bit.astype(jnp.int32) << i))

    zeros = jnp.zeros((m,), jnp.int32)
    src, dst = lax.fori_loop(0, scale, level, (zeros, zeros))
    return relabel(src, k_perm, scale), relabel(dst, k_perm, scale)


def relabel(x, key, scale: int):
    """A seeded bijection of ``[0, 2**scale)``: odd multiplies (int32
    products wrap, exact modulo 2**scale after the mask) and xor-shifts."""
    odd = jax.random.randint(key, (2,), 0, 1 << 30, jnp.int32) * 2 + 1
    mask = (1 << scale) - 1
    for i, shift in enumerate((scale // 2, scale // 3 + 1)):
        x = (x * odd[i]) & mask
        x = x ^ (x >> shift)
    return x
