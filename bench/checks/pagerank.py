"""Correctness of served PageRank: each answer against a float64 power
iteration over a scipy CSR matrix built from the benchmark's own edges.

Numbers compared, worst over the answers of a run:

* ``l1``: sum over vertices of |served rank - reference rank|.  The
  ranks sum to 1, so this is the share of the probability mass that is
  misplaced.
* ``iters_off``: |served iterations - reference iterations|.  The mix's
  tolerance never halts early, so both sides run ``max_iters``.

The control is the same reference with its rank vector held in bfloat16
between iterations, the precision step below the configuration's
float32 state.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp

# Limits, from readings on one v5e chip at the cell's own size (PERF.md,
# section 2): served float32 ranks read l1 <= 5.2e-7 over 12 kron-s20
# instances, the bfloat16-state control l1 >= 1.33e-3 over 3.
LIMITS = {"l1": 1e-4, "iters_off": 0}


def pagerank_reference_csr(adj: sp.csr_matrix, alpha: float, tol: float,
                           max_iters: int,
                           state_dtype=None) -> tuple[np.ndarray, int]:
    """Power iteration in float64, the service's formulation:
    x' = (1-a)/V + a * (A_norm^T x + dangling mass / V), halting once
    sum |x' - x| < tol * V.  Unit edge weights.  ``state_dtype`` rounds
    the rank vector to that type after every iteration (the control)."""
    V = adj.shape[0]
    outdeg = np.diff(adj.indptr).astype(np.float64)
    inv = np.divide(1.0, outdeg, out=np.zeros(V), where=outdeg > 0)
    dangling = outdeg == 0
    at = adj.T.tocsr()

    def held(v):
        if state_dtype is None:
            return v
        return v.astype(state_dtype).astype(np.float64)

    x = held(np.full(V, 1.0 / V))
    for it in range(max_iters):
        new = held((1 - alpha) / V + alpha * (at @ (x * inv)
                                              + x[dangling].sum() / V))
        if np.abs(new - x).sum() < tol * V:
            return new, it + 1
        x = new
    return x, max_iters


def reference(adj: sp.csr_matrix, params: dict):
    return pagerank_reference_csr(adj, params["alpha"], params["tol"],
                                  params["max_iters"])


def control(adj: sp.csr_matrix, params: dict) -> dict:
    """The reference in the program's place, its state in bfloat16."""
    return {"bfloat16_state": [pagerank_reference_csr(
        adj, params["alpha"], params["tol"], params["max_iters"],
        state_dtype=ml_dtypes.bfloat16)]}


def readings(answers: list, ref) -> dict:
    """``answers``: ``(ranks, iterations)`` pairs."""
    want, want_iters = ref
    l1 = iters_off = 0.0
    for value, iters in answers:
        got = np.asarray(value, dtype=np.float64)
        if got.shape != want.shape:
            return {"l1": float("inf"), "iters_off": float("inf")}
        d = float(np.abs(got - want).sum())
        l1 = max(l1, d if np.isfinite(d) else float("inf"))
        iters_off = max(iters_off, float(abs(int(iters) - want_iters)))
    return {"l1": l1, "iters_off": iters_off}
