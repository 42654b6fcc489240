"""The checks that decide ``correct``: the service's answers pass, a
perturbed answer and the lower-precision control fail."""
from __future__ import annotations

import numpy as np
import pytest

from bench import graphs, harness
from bench.tests.conftest import small_config
from repro.core import graph as G
from repro.core.query import GraphQuery
from repro.core.service import GraphAnalyticsService

CASES = [("kron-s20", "pagerank",
          {"alpha": 0.85, "tol": 1e-12, "max_iters": 10}),
         ("urand-s20", "pagerank",
          {"alpha": 0.85, "tol": 1e-12, "max_iters": 10}),
         ("kron-s20", "connected_components", {})]


def _within(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= v for k, v in limits.items())


@pytest.fixture(params=CASES, ids=lambda c: f"{c[0]}.{c[1]}")
def served(request):
    name, algorithm, params = request.param
    cfg = small_config(name)
    src, dst = graphs.generate(cfg, 21)
    n = graphs.n_vertices(cfg)
    svc = GraphAnalyticsService(cache_size=0, interactive_threshold_s=0.0)
    svc.add_graph("g", G.build_coo(src, dst, n, symmetrize=True))
    r = svc.result(svc.submit("g", GraphQuery.of(algorithm, **params)))
    check = graphs.load_plugin("checks", algorithm)
    adj = harness.reference_graph(src, dst, n)
    return check, adj, params, (np.asarray(r.value), r.iterations)


def test_service_answer_passes(served):
    check, adj, params, answer = served
    numbers = check.readings([answer], check.reference(adj, params))
    assert _within(numbers, check.LIMITS), numbers


def test_perturbed_answer_fails(served):
    check, adj, params, (value, iters) = served
    bad = value.copy()
    bad[0] += 0.01 if bad.dtype.kind == "f" else 1
    numbers = check.readings([(value, iters), (bad, iters)],
                             check.reference(adj, params))
    assert not _within(numbers, check.LIMITS), numbers


def test_control_fails(served):
    check, adj, params, _ = served
    ref = check.reference(adj, params)
    numbers = {name: check.readings(answers, ref)
               for name, answers in check.control(adj, params).items()}
    assert not all(_within(n, check.LIMITS) for n in numbers.values()), \
        numbers


def test_wrong_shape_or_nan_fails(served):
    check, adj, params, (value, iters) = served
    ref = check.reference(adj, params)
    short = check.readings([(value[:-1], iters)], ref)
    assert not _within(short, check.LIMITS)
    if value.dtype.kind == "f":
        nan = value.copy()
        nan[3] = np.nan
        assert not _within(check.readings([(nan, iters)], ref), check.LIMITS)
