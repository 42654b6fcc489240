"""Render the roofline table from the dry-run JSON records + the
analytic cost model (EXPERIMENTS.md §Roofline reads from this)."""
from __future__ import annotations

import glob
import json
import os

from benchmarks.common import csv_row
from repro.configs.base import SHAPES, get_config
from repro.utils.analytic import cost_cell
from repro.utils import roofline as RL
from repro.utils.compile_cache import enable_compile_cache

RESULTS = os.path.join(os.path.dirname(__file__), "results", "dryrun")

MESH_SIZES = {"single": {"data": 16, "model": 16},
              "multi": {"pod": 2, "data": 16, "model": 16}}


def load_records(results_dir=RESULTS):
    recs = []
    for f in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        with open(f) as fh:
            recs.append(json.load(fh))
    return recs


def analytic_row(arch: str, shape_name: str, mesh_kind: str,
                 microbatches: int = 8):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    sizes = MESH_SIZES[mesh_kind]
    dp = tuple(ax for ax in ("pod", "data")
               if ax in sizes and shape.global_batch % sizes[ax] == 0)
    # mirror usable_dp's sequential divisibility
    dp_used, rem = [], shape.global_batch
    for ax in ("pod", "data"):
        if ax in sizes and rem % sizes[ax] == 0:
            dp_used.append(ax)
            rem //= sizes[ax]
    cost = cost_cell(cfg, shape, sizes, dp_used=tuple(dp_used),
                     microbatches=microbatches if shape.kind == "train" else 1)
    terms = cost.terms()
    dominant = max(("compute_s", "memory_s", "collective_s"),
                   key=lambda k: terms[k])
    return cost, terms, dominant


def render(out=print, results_dir=RESULTS):
    recs = {(r["arch"], r["shape"],
             "multi" if r.get("mesh", "").count("x") == 2 else "single"): r
            for r in load_records(results_dir) if r.get("status") == "ok"}
    hdr = (f"{'arch':22s} {'shape':12s} {'mesh':6s} "
           f"{'compute_s':>10s} {'memory_s':>10s} {'coll_s':>10s} "
           f"{'dom':>6s} {'RF':>6s} {'mem/dev':>8s}")
    out(hdr)
    rows = []
    for (arch, shape, mesh_kind), r in sorted(recs.items()):
        try:
            cost, terms, dom = analytic_row(arch, shape, mesh_kind)
        except Exception as e:  # noqa: BLE001
            out(f"{arch} {shape} {mesh_kind}: analytic error {e}")
            continue
        bound = max(terms["compute_s"], terms["memory_s"],
                    terms["collective_s"])
        rf = terms["compute_ideal_s"] / bound if bound > 0 else 0.0
        rows.append({
            "arch": arch, "shape": shape, "mesh": mesh_kind,
            **{k: terms[k] for k in
               ("compute_s", "memory_s", "collective_s")},
            "dominant": dom.replace("_s", ""),
            "roofline_fraction": rf,
            "mem_gb": r.get("memory_per_device_gb", 0.0),
            "hlo_coll_counts": r.get("coll_counts", {}),
        })
        out(f"{arch:22s} {shape:12s} {mesh_kind:6s} "
            f"{RL.fmt_seconds(terms['compute_s']):>10s} "
            f"{RL.fmt_seconds(terms['memory_s']):>10s} "
            f"{RL.fmt_seconds(terms['collective_s']):>10s} "
            f"{rows[-1]['dominant']:>6s} {rf:6.2f} "
            f"{rows[-1]['mem_gb']:7.1f}G")
    return rows


def run(out=print):
    rows = render(out=lambda *_: None)
    for r in rows[:8]:
        out(csv_row(
            f"roofline/{r['arch']}_{r['shape']}_{r['mesh']}",
            max(r["compute_s"], r["memory_s"], r["collective_s"]),
            f"dom={r['dominant']};rf={r['roofline_fraction']:.2f}"))
    out(csv_row("roofline/n_cells", 0.0, f"count={len(rows)}"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    render()
