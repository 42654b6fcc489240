"""What the program's device scopes and host spans give the benchmark:
each operation's ``tf_op`` read from a recorded chip trace, device time
charged to ``pregel.*`` scopes, and ``pregel.dispatch_ms`` from the
program's dispatch spans.

``data/kron20_cc.xplane.pb`` was recorded before the program named its
phases, so its ``tf_op`` stacks hold no ``pregel.*`` scope.
"""
from __future__ import annotations

import json
import types
from pathlib import Path

import pytest

from bench import graphs, run, trace, xplane

TRACE = Path(__file__).resolve().parent / "data" / "kron20_cc.xplane.pb"
BENCH = Path(__file__).resolve().parents[1]


def test_reader_finds_the_tf_op_of_each_fusion():
    names = xplane.tf_ops(str(TRACE))["/device:TPU:0"]
    by_fusion = {op.split(" = ")[0]: tf_op for op, tf_op in names.items()}
    assert by_fusion["%fusion.41"] == "jit(body)/while/body/scatter-min"
    assert by_fusion["%fusion.42"] == "jit(body)/while/body/scatter-add"
    assert by_fusion["%fusion.40"] == "jit(body)/while/body/gather"
    # the keys are the names the profiler gives the device's op events
    assert set(names) <= set(trace.summarize(str(TRACE)).ops)


def test_scope_is_the_innermost_pregel_component():
    assert xplane.scope_of(
        "jit(body)/while/body/pregel.combine/scatter-min") == \
        "pregel.combine"
    assert xplane.scope_of("jit(body)/while/body/pregel.combine/"
                           "jit(superstep_ref)/pregel.gather/gather") == \
        "pregel.gather"
    assert xplane.scope_of("jit(body)/while/body/gather") is None
    assert xplane.strip_type("jit(body)/while/body/gather:") == \
        "jit(body)/while/body/gather"
    assert xplane.strip_type("jit(body)/while/body/gather") == \
        "jit(body)/while/body/gather"


def test_scope_seconds_charge_each_op_and_average_over_chips():
    summary = types.SimpleNamespace(n_devices=2, ops={
        "%fusion.1 = a": 4.0, "%fusion.2 = b": 2.0, "%fusion.3 = c": 1.0,
        "%copy.4 = d": 8.0})
    names = {"/device:TPU:0": {
        "%fusion.1 = a": "jit(body)/while/body/pregel.gather/gather",
        "%fusion.2 = b": "jit(body)/while/body/pregel.combine/scatter-add",
        "%fusion.3 = c": "jit(body)/while/body/pregel.combine/jit(clip)/min",
        "%copy.4 = d": "jit(body)/copy"}}
    assert xplane.scope_seconds(summary, names) == {
        "pregel.gather": 2.0, "pregel.combine": 1.5}
    recorded = trace.summarize(str(TRACE))
    assert xplane.scope_seconds(recorded, xplane.tf_ops(str(TRACE))) == {}


def _run_with_events(events, jobs=((10.0, 12.0), (12.0, 15.0))):
    tracer = types.SimpleNamespace(events=[(t1, k, {"t0": t0, "t1": t1})
                                           for k, t0, t1 in events])
    done = [types.SimpleNamespace(t_submit=a, t_done=b) for a, b in jobs]
    return types.SimpleNamespace(tracer=tracer, done_jobs=done)


def test_dispatch_ms_counts_the_window_spans_per_job():
    reader = graphs.load_plugin("metrics", "pregel.dispatch_ms", BENCH)
    spans = [("pregel.dispatch", 5.0, 6.0),      # the warm-up, outside
             ("pregel.dispatch", 10.5, 10.6),
             ("transfer", 11.0, 11.5),
             ("pregel.dispatch", 12.1, 12.4)]
    assert reader.read(_run_with_events(spans)) == pytest.approx(200.0)
    assert reader.read(_run_with_events(spans[2:3])) is None
    assert reader.read(types.SimpleNamespace(tracer=None,
                                             done_jobs=[])) is None


def test_traced_small_cell_reports_dispatch_ms(small_root, compile_cache,
                                               capsys):
    rc = run.main(["--workload", "kron-s10.wcc", "--seed", "3",
                   "--seconds", "0.3", "--trace", "1"],
                  require_accelerator=False, root=small_root)
    out, err = capsys.readouterr()
    assert rc == 0, err
    metrics = json.loads(out.strip().splitlines()[-1])["metrics"]
    assert metrics["pregel.dispatch_ms"]["unit"] == "ms"
    assert metrics["pregel.dispatch_ms"]["value"] > 0
