"""Public wrappers for the sorted-row intersection kernel.

``ell_intersect_counts`` takes the ``OrientedELL`` pieces directly
(``nbr`` row matrix + oriented edge endpoints), gathers the two row
tiles per edge *chunk* (bounding host/HBM footprint to
``2 * chunk_edges * K`` ints regardless of E), and routes each chunk
through the Pallas kernel (interpret mode on CPU hosts) or the pure-jnp
``searchsorted`` reference under the same signature — engines flip
implementations exactly like ``ell_combine``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ell_intersect.kernel import ell_intersect_pallas
from repro.kernels.ell_intersect.ref import ell_intersect_ref

_SUBLANE = 8
MAX_KERNEL_K = 2048      # beyond this the (R, K) tiles outgrow VMEM; ref


def _on_cpu() -> bool:
    return jax.devices()[0].platform == "cpu"


def _round_up(n, m):
    return ((n + m - 1) // m) * m


def ell_intersect(a, b, sentinel: int, block_edges: int = 256):
    """Pallas path (interpret on CPU) for one pair of ``(E, K)`` row
    tiles; the kernel reads them transposed (edges on lanes).

    Falls back to the reference when K exceeds the VMEM tile budget the
    kernel design assumes."""
    e, k = a.shape
    if k > MAX_KERNEL_K:
        return ell_intersect_ref(a, b, sentinel=sentinel)
    ep = _round_up(max(e, 1), block_edges)
    kp = _round_up(k, _SUBLANE)
    if (ep, kp) != (e, k):
        a = jnp.pad(a, ((0, ep - e), (0, kp - k)),
                    constant_values=sentinel)
        b = jnp.pad(b, ((0, ep - e), (0, kp - k)),
                    constant_values=sentinel)
    y = ell_intersect_pallas(a.T, b.T, sentinel=sentinel, k_valid=k,
                             block_edges=block_edges,
                             interpret=_on_cpu())
    return y[0, :e]


def ell_intersect_rows_ref(a, b, sentinel: int, block_edges: int = 256):
    """Reference path under the kernel's signature."""
    return ell_intersect_ref(a, b, sentinel=sentinel)


def ell_intersect_counts(oriented, use_pallas: bool = False,
                         chunk_edges: int = 1 << 18):
    """Per-oriented-edge intersection counts for a whole ``OrientedELL``.

    Returns an int64 numpy array of length ``oriented.n_edges`` (padding
    edges gather the all-sentinel row and are sliced off).  The total
    triangle count is its sum.
    """
    import numpy as np

    nbr = oriented.nbr
    sentinel = oriented.n_vertices
    path = ell_intersect if use_pallas else ell_intersect_rows_ref
    out = []
    n = int(oriented.eu.shape[0])
    for lo in range(0, n, chunk_edges):
        eu = jax.lax.slice(oriented.eu, (lo,), (min(lo + chunk_edges, n),))
        ev = jax.lax.slice(oriented.ev, (lo,), (min(lo + chunk_edges, n),))
        a = jnp.take(nbr, eu, axis=0)      # sentinel edges hit the
        b = jnp.take(nbr, ev, axis=0)      # all-sentinel row -> count 0
        out.append(np.asarray(path(a, b, sentinel)))
    counts = np.concatenate(out) if out else np.zeros(0, np.int32)
    return counts[: oriented.n_edges].astype(np.int64)
