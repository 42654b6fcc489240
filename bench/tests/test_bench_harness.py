"""Whole runs of small cells on the CPU, the device check skipped: the
result line, a cell added by files alone, the refusal without an
accelerator, and ``correct`` coming out false when the timed path is
broken underneath."""
from __future__ import annotations

import importlib
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import calibrate, harness, run
from repro.core import engines
from repro.core import graph as G

# the package re-exports functions under these names: take the modules
cc_mod = importlib.import_module("repro.core.algorithms.connected_components")
pr_mod = importlib.import_module("repro.core.algorithms.pagerank")

CELLS = ("kron-s10.pagerank", "urand-s10.pagerank", "kron-s10.wcc")
SECONDS = "0.3"


def run_cell(root, cell, capsys, seed=4, trace=0):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   SECONDS, "--trace", str(trace)],
                  require_accelerator=False, root=root)
    out, err = capsys.readouterr()
    return rc, out, err


def result_of(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_small_cell_runs_and_is_correct(small_root, compile_cache, capsys,
                                        cell):
    rc, out, err = run_cell(small_root, cell, capsys)
    assert rc == 0, err
    res = result_of(out)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"evps", "setup_s"}   # no HBM on a CPU
    assert res["metrics"]["evps"]["unit"] == "EV/s"
    assert res["device"]["platform"] == "cpu"
    last = err.strip().splitlines()
    assert last[-1] == "correct = True"
    for name, c in res["checks"].items():
        assert f"check {name} = {c['value']!r} (limit {c['limit']!r})" \
            in last


@pytest.mark.parametrize("cell,programs", [("kron-s10.pagerank", 1.0),
                                           ("kron-s10.wcc", 0.0)])
def test_traced_run_counts_programs_per_job(small_root, compile_cache,
                                            capsys, cell, programs):
    rc, out, err = run_cell(small_root, cell, capsys, trace=1)
    assert rc == 0, err
    res = result_of(out)
    assert res["correct"] is True
    metrics = res["metrics"]
    # the PageRank runner builds its Pregel program anew in every job
    assert metrics["pregel.programs_per_job"]["value"] == programs
    assert metrics["pregel.programs_per_job"]["unit"] == "programs"
    assert metrics["pregel.supersteps"]["value"] >= 1
    assert "peak_hbm_bytes" not in metrics       # an end-to-end metric


def test_memory_sampler_keeps_the_window_peak():
    class Chip:
        def __init__(self, readings):
            self.readings = iter(readings)
            self.last = {"bytes_in_use": 0}

        def memory_stats(self):
            self.last = next(self.readings, self.last)
            return self.last

    # arrays in use plus the runtime's reservation for program scratch
    chips = [Chip([{"bytes_in_use": 10}, {"bytes_in_use": 30},
                   {"bytes_in_use": 20}]),
             Chip([{"bytes_in_use": 5, "bytes_reserved": 0},
                   {"bytes_in_use": 25, "bytes_reserved": 15},
                   {"bytes_in_use": 7, "bytes_reserved": 15}])]
    with harness.MemorySampler(chips, interval_s=0.001) as sampler:
        time.sleep(0.05)
    assert sampler.peak == 40
    with harness.MemorySampler([type("Cpu", (), {
            "memory_stats": lambda self: None})()]) as none:
        pass
    assert none.peak is None


def test_a_new_mix_is_a_file_and_an_entry(small_root, compile_cache,
                                          capsys):
    mix = json.loads((small_root / "bench/mixes/pagerank.json").read_text())
    mix.update(name="pagerank-5", params={**mix["params"], "max_iters": 5})
    (small_root / "bench/mixes/pagerank-5.json").write_text(json.dumps(mix))
    bench = json.loads((small_root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "urand-s10.pagerank-5",
                               "config": "urand-s10",
                               "traffic": "pagerank-5", "chips": 1,
                               "why": "five iterations"})
    (small_root / "BENCHMARK.json").write_text(json.dumps(bench))
    rc, out, err = run_cell(small_root, "urand-s10.pagerank-5", capsys)
    assert rc == 0, err
    res = result_of(out)
    assert res["correct"] is True
    assert res["checks"]["iters_off"]["value"] == 0


def test_refuses_without_an_accelerator(small_root, capsys):
    rc = run.main(["--workload", "kron-s10.pagerank", "--seed", "1",
                   "--seconds", "1"], root=small_root)
    out, err = capsys.readouterr()
    assert rc == 2 and out == ""
    assert "no accelerator" in err


def _state_unchanged(monkeypatch):
    """Every Pregel loop returns its initial state."""
    def stub(spec, graph, init_state, max_iters, *a, **k):
        return init_state, jnp.int32(max_iters)
    for mod in (engines, pr_mod, cc_mod):
        monkeypatch.setattr(mod, "run_pregel", stub)
    monkeypatch.setattr(engines, "run_pregel_frontier", stub)
    monkeypatch.setattr(engines, "run_pregel_fused", stub)


def _half_the_edges(monkeypatch):
    """The engines see every other edge slot of the served graph."""
    init = engines.Engine.__init__

    def halved(self, coo, *a, **k):
        src = np.asarray(coo.src)[: coo.n_edges][::2]
        dst = np.asarray(coo.dst)[: coo.n_edges][::2]
        half = G.build_coo(src, dst, coo.n_vertices)
        half.symmetric = coo.symmetric
        init(self, half, *a, **k)
    monkeypatch.setattr(engines.Engine, "__init__", halved)


def _answer_altered(monkeypatch):
    """One vertex's answer is changed where the engine produces it."""
    run_ = engines.Engine.run

    def altered(self, *a, **k):
        r = run_(self, *a, **k)
        v = r.value
        r.value = v.at[0].add(0.01 if v.dtype.kind == "f" else 1)
        return r
    monkeypatch.setattr(engines.Engine, "run", altered)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_the_edges": _half_the_edges,
          "answer_altered": _answer_altered}


BROKEN = [(f, c) for f in sorted(FAULTS) for c in CELLS]


@pytest.mark.parametrize("fault,cell", BROKEN)
def test_broken_timed_path_is_not_correct(small_root, compile_cache, capsys,
                                          monkeypatch, fault, cell):
    FAULTS[fault](monkeypatch)
    rc, out, err = run_cell(small_root, cell, capsys)
    assert rc == 0, err
    res = result_of(out)
    assert res["correct"] is False, res["checks"]
    assert err.strip().splitlines()[-1] == "correct = False"


@pytest.mark.parametrize("cell", CELLS)
def test_calibration_reads_program_below_and_control_above(
        small_root, compile_cache, cell):
    lines = []
    rc = calibrate.main(["--workload", cell, "--seeds", "1-2",
                         "--control-seeds", "3"],
                        require_accelerator=False, root=small_root,
                        out=lines.append)
    assert rc == 0
    last = json.loads(lines[-1])
    limits = last["limits"]
    assert all(last["program_max"][k] <= v for k, v in limits.items())
    assert any(last["control_min"][f"{name}.{k}"] > v
               for name in {key.split(".")[0]
                            for key in last["control_min"]}
               for k, v in limits.items()
               if f"{name}.{k}" in last["control_min"])
