"""Benchmark driver — one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--paper] [--only fig9,fig13]

Prints ``name,us_per_call,derived`` CSV.  Default scale finishes on a laptop
CPU in minutes; ``--paper`` restores the paper's workload sizes.
"""
from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, "src")

from benchmarks.common import Csv  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402


def main(argv=None) -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true",
                    help="paper-scale workloads (slower)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration: every section runs in "
                         "seconds (import/API drift canary, not a benchmark)")
    ap.add_argument("--only", default="",
                    help="comma list: fig9,fig11,fig12,fig13,fig14,fig15,"
                         "roofline,prewarm")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    only = set(args.only.split(",")) if args.only else None
    csv = Csv()
    from benchmarks import (fig9_act, fig11_ddl, fig12_ablation, fig13_cache,
                            fig14_prewarm, fig15_overhead, prewarm, roofline)
    table = {"fig9": fig9_act, "fig11": fig11_ddl, "fig12": fig12_ablation,
             "fig13": fig13_cache, "fig14": fig14_prewarm,
             "fig15": fig15_overhead, "roofline": roofline,
             "prewarm": prewarm}
    if only and (unknown := only - set(table)):
        # a typo'd section must not silently no-op (CI would stay green)
        ap.error(f"unknown --only section(s): {sorted(unknown)}; "
                 f"known: {sorted(table)}")
    for name, mod in table.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        mod.run(csv, paper_scale=args.paper, seed=args.seed, smoke=args.smoke)
        csv.add(f"{name}/bench_wall", 1e6 * (time.perf_counter() - t0), "")
    csv.dump()


if __name__ == "__main__":
    main()
