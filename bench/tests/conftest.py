"""Fixtures: a throwaway benchmark root holding small copies of the
cells, and JAX's compilation cache kept inside the test's own directory."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

# Small stand-ins for the real configurations: scale 10, and as many
# distinct pairs as every seed's draw holds at that scale.
SMALL = {"kron-s20": ("kron-s10", 9728), "urand-s20": ("urand-s10", 15872)}


def small_config(name: str) -> dict:
    small, pairs = SMALL[name]
    with open(BENCH / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    cfg.update(name=small, scale=10, undirected_edges=pairs,
               n_vertices=1024, edge_slots=2 * pairs)
    return cfg


@pytest.fixture
def small_root(tmp_path) -> Path:
    """A root with BENCHMARK.json and bench/ whose cells are the real ones
    on scale-10 graphs: the same mixes, checks, generators and readers."""
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = []
    for cell in bench["workloads"]:
        cfg = small_config(cell["config"])
        with open(root / "bench" / "configs" / f"{cfg['name']}.json",
                  "w") as f:
            json.dump(cfg, f)
        cells.append({**cell, "config": cfg["name"],
                      "name": f"{cfg['name']}.{cell['traffic']}"})
    bench["workloads"] = cells
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [c["name"] for c in cells]
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture
def compile_cache(tmp_path, monkeypatch):
    """Point the harness's persistent compilation cache into the test's
    directory, and turn it off again afterwards for the tests that follow
    in this process."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    # JAX reads the variable once, at start-up; this process has started
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jax"))
    yield tmp_path / "jax"
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()
