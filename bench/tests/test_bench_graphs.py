"""The device-side GAP generators: seeded, loop-free, duplicate-free,
exactly sized, and skewed (kron) or not (urand)."""
from __future__ import annotations

import numpy as np
import pytest

from bench import graphs
from bench.tests.conftest import small_config
from repro.core import graph as G

CONFIGS = ("kron-s20", "urand-s20")


def _pairs(src, dst, n):
    lo, hi = np.minimum(src, dst), np.maximum(src, dst)
    return lo.astype(np.int64) * n + hi


@pytest.mark.parametrize("name", CONFIGS)
def test_seed_orders_the_stream_of_one_instance(name):
    cfg = small_config(name)
    a = graphs.generate(cfg, 5)
    b = graphs.generate(cfg, 5)
    c = graphs.generate(cfg, 6)
    other = graphs.generate(cfg, 5, instance_seed=cfg["instance_seed"] + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    n = cfg["n_vertices"]
    assert np.array_equal(np.sort(_pairs(*a, n)), np.sort(_pairs(*c, n)))
    assert not np.array_equal(np.sort(_pairs(*a, n)),
                              np.sort(_pairs(*other, n)))


@pytest.mark.parametrize("name", CONFIGS)
def test_no_self_loops_no_duplicates_exact_count(name):
    cfg = small_config(name)
    src, dst = graphs.generate(cfg, 11)
    assert src.shape == dst.shape == (cfg["undirected_edges"],)
    assert np.all(src != dst)
    assert np.all((np.minimum(src, dst) >= 0)
                  & (np.maximum(src, dst) < cfg["n_vertices"]))
    pairs = _pairs(src, dst, cfg["n_vertices"])
    assert np.unique(pairs).size == pairs.size
    # both directions occur in the stream
    assert 0.3 < np.mean(src < dst) < 0.7


@pytest.mark.parametrize("name", CONFIGS)
def test_service_graph_holds_the_configured_slots(name):
    cfg = small_config(name)
    src, dst = graphs.generate(cfg, 12)
    coo = G.build_coo(src, dst, graphs.n_vertices(cfg), symmetrize=True)
    assert coo.n_vertices == cfg["n_vertices"] == 1024
    assert coo.n_edges == coo.e_pad == cfg["edge_slots"]


def test_seeds_beyond_32_bits_are_distinct():
    cfg = small_config("urand-s20")
    low = graphs.generate(cfg, 1, instance_seed=7)[0]
    high = graphs.generate(cfg, 1, instance_seed=7 + 2 ** 32)[0]
    huge = graphs.generate(cfg, 2 ** 40 + 3, instance_seed=2 ** 40 + 3)[0]
    assert not np.array_equal(low, high)
    assert huge.shape == low.shape
    with pytest.raises(ValueError):
        graphs.prng_key(-1)


def _degree_skew(name: str) -> float:
    cfg = {**small_config(name), "scale": 12, "n_vertices": 4096,
           "undirected_edges": 4096 * 8}
    src, dst = graphs.generate(cfg, 3)
    deg = np.bincount(np.concatenate([src, dst]), minlength=4096)
    return deg.max() / deg.mean()


def test_kron_is_skewed_and_urand_is_not():
    assert _degree_skew("kron-s20") > 20
    assert _degree_skew("urand-s20") < 3


def test_distinct_pairs_keeps_the_first_ones_in_draw_order():
    src = np.array([3, 1, 2, 2, 4, 0, 5], np.int32)
    dst = np.array([1, 3, 2, 0, 0, 2, 6], np.int32)
    # (1,3) twice, a loop (2,2), (0,2) twice (once reversed)
    lo, hi = graphs.distinct_pairs(src, dst, 8, 4)
    assert list(zip(lo.tolist(), hi.tolist())) == [(1, 3), (0, 2), (0, 4),
                                                   (5, 6)]


def test_kron_relabel_is_a_bijection():
    import jax
    import jax.numpy as jnp
    kron = graphs.load_plugin("generators", "kron")
    ids = jnp.arange(1 << 12, dtype=jnp.int32)
    out = np.asarray(kron.relabel(ids, jax.random.key(3), 12))
    assert sorted(out.tolist()) == list(range(1 << 12))
    assert not np.array_equal(out, np.arange(1 << 12))


def test_too_few_distinct_pairs_is_an_error():
    cfg = {**small_config("kron-s20"), "undirected_edges": 16384}
    with pytest.raises(ValueError, match="distinct undirected pairs"):
        graphs.generate(cfg, 1)
