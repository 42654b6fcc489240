"""Seeded graphs for the benchmark.

A configuration file names its generator (``bench/generators/<name>.py``),
which draws the raw directed edge list ``(src, dst)`` on the device from a
PRNG key in one jitted call; the generator's ``PARAMS`` name the
configuration keys it takes.  On the host, :func:`distinct_pairs` turns
the draw into exactly ``undirected_edges`` distinct undirected pairs:
self-loops dropped, duplicates (in either direction) dropped, and of what
is left the first ``undirected_edges`` in the order they were drawn.

The configuration fixes the instance (``instance_seed``), as GAP fixes its
graphs: a connected-components job's supersteps depend on the instance
(4 to 6 on scale-20 draws), so a graph drawn from each run's seed would
change a run's work by a fifth.  A run's ``--seed`` orders the stream in
which the instance's edges reach the service, and the direction of each;
the service's COO, and every job's work, are the same for every seed.

The deduplication runs on the host because a sort of 1.7e7 keys takes the
TPU compiler a minute or more, while one packed 64-bit ``np.sort`` takes
about a second.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import numpy as np

BENCH_DIR = Path(__file__).resolve().parent


def prng_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (seeds may exceed 32
    bits): the low and high 32-bit halves both enter it."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    hi, lo = divmod(int(seed) % (1 << 64), 1 << 32)
    return jax.random.fold_in(jax.random.key(lo), hi)


def load_plugin(kind: str, name: str, bench_dir: Path = BENCH_DIR):
    """Import ``<bench_dir>/<kind>/<name>.py`` by path (names may hold
    dots)."""
    path = Path(bench_dir) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def distinct_pairs(src: np.ndarray, dst: np.ndarray, n_vertices: int,
                   n_keep: int) -> tuple[np.ndarray, np.ndarray]:
    """The first ``n_keep`` distinct undirected non-loop pairs of
    ``(src, dst)`` in draw order, as int32 ``(lo, hi)`` with ``lo < hi``.
    Raises when the draw holds fewer."""
    lo = np.minimum(src, dst).astype(np.uint64)
    hi = np.maximum(src, dst).astype(np.uint64)
    id_bits = max(int(n_vertices - 1).bit_length(), 1)
    pos_bits = max(int(src.shape[0] - 1).bit_length(), 1)
    if 2 * id_bits + pos_bits > 64:
        raise ValueError(f"{n_vertices} vertices and {src.shape[0]} draws "
                         f"do not pack into 64-bit keys")
    # pair in the high bits, draw position in the low: one sort puts each
    # pair's copies together, earliest draw first
    keys = np.sort(((lo << np.uint64(id_bits)) | hi) << np.uint64(pos_bits)
                   | np.arange(src.shape[0], dtype=np.uint64))
    pair = keys >> np.uint64(pos_bits)
    first = np.empty(keys.shape[0], bool)
    first[0] = True
    np.not_equal(pair[1:], pair[:-1], out=first[1:])
    pos = (keys[first] & np.uint64((1 << pos_bits) - 1)).astype(np.int64)
    pos = np.sort(pos[src[pos] != dst[pos]])
    if pos.shape[0] < n_keep:
        raise ValueError(f"the draw held {pos.shape[0]} distinct undirected "
                         f"pairs, fewer than the configuration's {n_keep}")
    pos = pos[:n_keep]
    return (np.minimum(src[pos], dst[pos]).astype(np.int32),
            np.maximum(src[pos], dst[pos]).astype(np.int32))


@functools.lru_cache(maxsize=None)
def _raw_fn(gen_path: Path, params: tuple):
    gen = load_plugin("generators", gen_path.stem, gen_path.parent.parent)
    return jax.jit(functools.partial(gen.raw_edges, **dict(params)))


def generate(config: dict, seed: int, bench_dir: Path = BENCH_DIR,
             instance_seed: int | None = None
             ) -> tuple[np.ndarray, np.ndarray]:
    """The configuration's graph instance (or ``instance_seed``'s) as the
    edge stream ``seed`` orders: host int32 ``(src, dst)`` vectors of
    exactly ``config['undirected_edges']`` distinct undirected pairs."""
    gen = load_plugin("generators", config["generator"], bench_dir)
    params = tuple((k, config[k]) for k in gen.PARAMS)
    fn = _raw_fn(Path(bench_dir) / "generators"
                 / f"{config['generator']}.py", params)
    if instance_seed is None:
        instance_seed = int(config["instance_seed"])
    src, dst = jax.device_get(fn(prng_key(instance_seed)))
    lo, hi = distinct_pairs(src, dst, n_vertices(config),
                            int(config["undirected_edges"]))
    rng = np.random.default_rng(seed)
    order = rng.permutation(lo.shape[0])
    flip = rng.random(lo.shape[0]) < 0.5
    lo, hi = lo[order], hi[order]
    return np.where(flip, hi, lo), np.where(flip, lo, hi)


def n_vertices(config: dict) -> int:
    return 1 << int(config["scale"])
