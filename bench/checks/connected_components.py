"""Correctness of served connected components: each answer's labels
against scipy's components of the benchmark's own edges, canonicalized to
the smallest vertex id of each component (the service's labels).

Number compared, worst over the answers of a run:

* ``labels_differ``: vertices whose served label is not the reference's.
  Exact: limit 0.

The control is hash-to-min label propagation, the service's algorithm,
with its labels held in bfloat16 (the step below
the configuration's int32 labels that a narrowed message channel would
take; see ``control``).
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp
from scipy.sparse import csgraph

LIMITS = {"labels_differ": 0}


def cc_reference(adj: sp.csr_matrix) -> np.ndarray:
    """Component labels, canonicalized to the component's min vertex id
    (the service's hash-to-min labels)."""
    _, lab = csgraph.connected_components(adj, directed=False)
    first = np.unique(lab, return_index=True)[1]    # min id per label
    return first[lab].astype(np.int32)


def reference(adj: sp.csr_matrix, params: dict):
    return cc_reference(adj)


def propagate(adj: sp.csr_matrix, label_dtype=None) -> np.ndarray:
    """Hash-to-min label propagation: every vertex takes the least label
    among itself and its neighbours, until no label changes.
    ``label_dtype`` holds the labels in that type between rounds."""
    V = adj.shape[0]
    has_nbr = np.diff(adj.indptr) > 0
    starts = adj.indptr[:-1][has_nbr]

    def held(v):
        return v if label_dtype is None else \
            v.astype(label_dtype).astype(np.float64)

    lab = held(np.arange(V, dtype=np.float64))
    while True:
        new = lab.copy()
        new[has_nbr] = np.minimum(
            new[has_nbr], np.minimum.reduceat(lab[adj.indices], starts))
        new = held(new)
        if np.array_equal(new, lab):
            return lab
        lab = new


def control(adj: sp.csr_matrix, params: dict) -> dict:
    """The propagation in the program's place, its labels in bfloat16."""
    return {"bfloat16_labels": [(propagate(adj, ml_dtypes.bfloat16), None)]}


def readings(answers: list, ref) -> dict:
    """``answers``: ``(labels, iterations)`` pairs."""
    worst = 0
    for value, _ in answers:
        got = np.asarray(value)
        if got.shape != ref.shape:
            return {"labels_differ": float("inf")}
        worst = max(worst, int(np.sum(got != ref)))
    return {"labels_differ": worst}
