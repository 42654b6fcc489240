"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

A TPU trace has one plane per chip (``/device:TPU:<n>``) whose
``XLA Ops`` line holds every operation the chip ran and whose
``XLA Modules`` line holds every program execution, and host planes
(``/host:CPU``) with one line per thread, where ``jax.profiler``
annotations such as the harness's ``bench.submit`` land.  Both are on
one clock.

* window: from the first ``bench.submit`` to the last
  ``bench.block_until_ready`` on the host (the whole trace where the
  harness left no annotations);
* busy: the union of the intervals of a chip's operations inside the
  window, averaged over the chips;
* per-operation device time, less the time of the operations nested in
  it, and per-program device time, both summed over the chips;
* the Pregel program's device time: the programs whose name matches
  ``PREGEL_PROGRAMS``, averaged over the chips;
* idle gaps: the stretches of the window in which a chip ran nothing,
  each named by what the host was doing then: the innermost host event
  covering the gap's middle, under the harness annotation around it.
"""
from __future__ import annotations

import dataclasses
import glob
import os

# Programs that hold a Pregel loop.  ``run_pregel``, ``run_pregel_fused``
# and ``run_pregel_frontier`` jit an inner function named ``body``; a
# name scope or program named for Pregel counts too.
PREGEL_PROGRAMS = ("jit_body", "pregel")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"
HOST_PREFIX = "/host:"
WINDOW_START = "bench.submit"
WINDOW_END = "bench.block_until_ready"
ANNOTATION_PREFIX = "bench."
TOP = 10


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # mean over chips
    program_s: float              # Pregel programs, mean over chips
    n_devices: int
    ops: dict                     # op name -> self seconds, all chips
    modules: dict                 # program name -> device seconds
    idle_gaps: list               # the TOP longest: [(host activity, s)]

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:TOP]]}


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _union(intervals: list) -> list:
    """Merge ``(t0, t1)`` intervals; returns them sorted and disjoint."""
    out = []
    for t0, t1 in sorted(intervals):
        if out and t0 <= out[-1][1]:
            if t1 > out[-1][1]:
                out[-1][1] = t1
        else:
            out.append([t0, t1])
    return out


def _self_times(events: list, lo: float, hi: float) -> list:
    """``(name, seconds)`` of each event inside ``[lo, hi]``, less the
    time of the events nested in it (a ``while`` op holds its body's)."""
    out = []
    stack = []                  # [name, start, end, child time]
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        while stack and a >= stack[-1][2]:
            n, a0, b0, kids = stack.pop()
            out.append((n, (b0 - a0 - kids) * 1e-9))
        if stack:
            stack[-1][3] += b - a
        stack.append([name, a, b, 0.0])
    out.extend((n, (b0 - a0 - kids) * 1e-9) for n, a0, b0, kids in stack)
    return out


def _clip(intervals, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _host_name(host: list, annotations: list, t: float) -> str:
    """What the host was doing at ``t``: the innermost host event over
    it, under the innermost harness annotation over it."""
    inner = min((e for e in host if e[1] <= t <= e[2]),
                key=lambda e: e[2] - e[1], default=None)
    ann = min((e for e in annotations if e[1] <= t <= e[2]),
              key=lambda e: e[2] - e[1], default=None)
    if inner is None:
        return ann[0] if ann else "host: nothing traced"
    if ann is None or ann is inner:
        return inner[0]
    return f"{ann[0]} > {inner[0]}"


def summarize(path: str) -> Summary:
    """Reduce the trace at ``path``."""
    from jax.profiler import ProfileData
    profile_data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in profile_data.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith(DEVICE_PREFIX) and OPS_LINE in lines:
            devices.append((_events(lines[OPS_LINE]),
                            _events(lines[MODULES_LINE])
                            if MODULES_LINE in lines else []))
        elif plane.name.startswith(HOST_PREFIX):
            for ln in plane.lines:
                host.extend(_events(ln))
    if not devices:
        raise ValueError(f"{path}: no device plane with an {OPS_LINE!r} "
                         f"line")
    annotations = [e for e in host if e[0].startswith(ANNOTATION_PREFIX)]
    starts = [e[1] for e in annotations if e[0] == WINDOW_START]
    ends = [e[2] for e in annotations if e[0] == WINDOW_END]
    if starts and ends:
        lo, hi = min(starts), max(ends)
    else:
        every = [e for ops, _ in devices for e in ops]
        lo, hi = min(e[1] for e in every), max(e[2] for e in every)
    busy = program = 0.0
    ops_s: dict = {}
    modules_s: dict = {}
    gaps = []
    for ops, modules in devices:
        inside = [e for e in ops if e[2] > lo and e[1] < hi]
        merged = _union(_clip([(e[1], e[2]) for e in inside], lo, hi))
        busy += sum(b - a for a, b in merged)
        for name, d in _self_times(inside, lo, hi):
            ops_s[name] = ops_s.get(name, 0.0) + d
        for name, a, b in modules:
            if b <= lo or a >= hi:
                continue
            d = (min(b, hi) - max(a, lo)) * 1e-9
            modules_s[name] = modules_s.get(name, 0.0) + d
            if any(p in name for p in PREGEL_PROGRAMS):
                program += d
        edges = [lo] + [t for ab in merged for t in ab] + [hi]
        gaps.extend((a, b) for a, b in zip(edges[0::2], edges[1::2])
                    if b > a)
    n = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_host_name(host, annotations, (a + b) / 2), (b - a) * 1e-9)
             for a, b in gaps[:TOP]]
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n,
                   program_s=program / n, n_devices=n, ops=ops_s,
                   modules=modules_s, idle_gaps=named)
