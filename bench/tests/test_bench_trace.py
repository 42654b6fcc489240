"""The trace reduction, on a recorded chip trace, and the roofline
arithmetic.

``data/kron20_cc.xplane.pb`` is one warm connected-components job on the
kron-s20 graph, traced on one TPU v5e chip: five dense supersteps in one
``jit_body`` program of 4,129.645877 ms (its ``XLA Modules`` event), the
gather and the two segment scatters ~1.35 s each.
"""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import roofline, trace

TRACE = Path(__file__).resolve().parent / "data" / "kron20_cc.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace.summarize(str(TRACE))


def test_device_time_of_the_pregel_program(summary):
    assert summary.n_devices == 1
    assert summary.program_s == pytest.approx(4.129645877, rel=1e-6)
    assert any(k.startswith("jit_body") for k in summary.modules)


def test_busy_within_window_and_ops_account_for_it(summary):
    # no harness annotations in this trace: the window is the device's
    # first to last operation
    assert summary.window_s == pytest.approx(4.13046616, rel=1e-6)
    assert 0 < summary.busy_s <= summary.window_s
    assert summary.busy_s == pytest.approx(4.129652254, rel=1e-6)
    assert sum(summary.ops.values()) == pytest.approx(summary.busy_s,
                                                      rel=1e-3)


def test_breakdown_names_the_scatters_and_gather_first(summary):
    b = summary.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    top = [name.split(" = ")[0] for name, _ in b["device_ops"][:3]]
    assert sorted(top) == ["%fusion.40", "%fusion.41", "%fusion.42"]
    assert all(1.3 < s < 1.4 for _, s in b["device_ops"][:3])
    # the while loop holds the supersteps: its own time is small
    loop = [s for name, s in summary.ops.items()
            if name.startswith("%while")]
    assert loop and loop[0] < 0.05
    assert all(isinstance(n, str) and s >= 0 for n, s in b["idle_gaps"])
    assert sum(s for _, s in b["idle_gaps"]) <= (
        summary.window_s - summary.busy_s) + 1e-9


def test_union_and_self_times():
    assert trace._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3],
                                                              [5, 8]]
    ev = [("loop", 0, 100), ("a", 10, 40), ("b", 50, 90), ("c", 120, 130)]
    got = dict(trace._self_times(ev, 0, 1000))
    assert got == pytest.approx({"loop": 30e-9, "a": 30e-9, "b": 40e-9,
                                 "c": 10e-9})


def test_peaks_are_known_or_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert roofline.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")


def test_superstep_bytes():
    # 10 slots x (two 4-byte ids + gathered and combined 4-byte state)
    # + 4 vertices x (read + write of 4 bytes)
    assert roofline.superstep_bytes(4, 10, state_itemsize=4) == 192
    assert roofline.superstep_bytes(4, 10, state_itemsize=4,
                                    reads_weight=True) == 232
    assert roofline.superstep_bytes(4, 10, state_itemsize=4,
                                    state_width=2) == 10 * 24 + 4 * 16
