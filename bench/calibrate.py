"""Readings that a cell's correctness limits are set from.

    python bench/calibrate.py --workload kron-s20.pagerank --seeds 1-12 --control-seeds 13-15

For each ``--seeds`` seed: a graph instance of the cell's configuration
drawn from that seed (a run serves the configuration's one instance; these
readings cover many), one job of its mix through a fresh service exactly
as a run's window drives it, and the check's numbers against the
reference.  For each ``--control-seeds`` seed: the
check's control (the reference in the precision below the
configuration's) in the program's place, compared the same way.  All in
one process, so the programs compile once.  Prints one JSON line per
seed, and the largest program reading and smallest control reading of
each number last.  The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def seed_list(text: str) -> list:
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out.extend(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def readings_for_seed(config, mix, check, seed, control, bench_dir) -> dict:
    from repro.core import graph as G
    from repro.core.query import GraphQuery
    from repro.core.service import GraphAnalyticsService

    from bench import graphs, harness

    n = graphs.n_vertices(config)
    src, dst = graphs.generate(config, seed, bench_dir, instance_seed=seed)
    out = {"seed": seed, "kind": "control" if control else "program"}
    if not control:
        coo = G.build_coo(src, dst, n, symmetrize=bool(config["symmetrize"]))
        svc = GraphAnalyticsService(
            interactive_threshold_s=float(mix["interactive_threshold_s"]),
            **config["service"])
        svc.add_graph(harness.GRAPH_NAME, coo)
        query = GraphQuery.of(mix["algorithm"],
                              count_only=bool(mix["count_only"]),
                              **mix["params"])
        job = harness.one_job(svc, query)
        del svc, coo
        if job.error is not None:
            return {**out, "error": job.error}
        out.update(wall_s=job.wall_s, iterations=job.iterations,
                   variant=job.variant)
    adj = harness.reference_graph(src, dst, n)
    ref = check.reference(adj, mix["params"])
    if control:
        out["readings"] = {
            name: check.readings(answers, ref)
            for name, answers in check.control(adj, mix["params"]).items()}
    else:
        out["readings"] = check.readings([(job.value, job.iterations)], ref)
    return out


def main(argv=None, require_accelerator: bool = True, root: Path = ROOT,
         out=print) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    from bench import graphs, harness
    bench_dir = Path(root) / "bench"
    cell = harness.find_cell(harness.load_benchmark(root), args.workload)
    config = harness.load_json(bench_dir / "configs"
                               / f"{cell['config']}.json")
    mix = harness.load_json(bench_dir / "mixes" / f"{cell['traffic']}.json")
    check = graphs.load_plugin("checks", mix["algorithm"], bench_dir)
    try:
        devices = harness.chips_for(cell, require_accelerator)
    except harness.SetupError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    worst: dict = {}
    least: dict = {}
    for control, seeds in ((False, seed_list(args.seeds)),
                           (True, seed_list(args.control_seeds))):
        for seed in seeds:
            t0 = time.perf_counter()
            rec = readings_for_seed(config, mix, check, seed, control,
                                    bench_dir)
            rec["seconds"] = time.perf_counter() - t0
            rec["device"] = devices[0].device_kind
            out(json.dumps(rec))
            if control:
                for name, numbers in rec.get("readings", {}).items():
                    for k, v in numbers.items():
                        key = f"{name}.{k}"
                        least[key] = min(least.get(key, v), v)
            else:
                for k, v in rec.get("readings", {}).items():
                    worst[k] = max(worst.get(k, v), v)
    out(json.dumps({"program_max": worst, "control_min": least,
                    "limits": check.LIMITS}))
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())
