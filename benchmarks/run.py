# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows (benchmarks.common.csv_row).
from __future__ import annotations

import sys
import traceback

from repro.utils.compile_cache import enable_compile_cache


def main() -> None:
    from benchmarks import (fig5_engine_crossover, fig6_multi_account,
                            fig7_connected_users, table1_maxadjacentnodes,
                            algo_suite, kernels_bench, roofline_report)
    print("name,us_per_call,derived")
    ok = True
    for mod in (fig5_engine_crossover, fig6_multi_account,
                fig7_connected_users, table1_maxadjacentnodes,
                algo_suite, kernels_bench, roofline_report):
        try:
            mod.run(out=print)
        except Exception:   # noqa: BLE001 — keep the harness going
            ok = False
            print(f"{mod.__name__},0.0,ERROR")
            traceback.print_exc()
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
