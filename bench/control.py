#!/usr/bin/env python3
"""Read the compared numbers of the program and of its control over many
seeds of one cell, in one process (set-up compiles once).

For each seed the cell runs as the benchmark runs it, with a window of
``--seconds``; the check then reads each number twice on the same sampled
dispatches: the program against the plain reference (the lower reading)
and the control against it (the reference computed at the next precision
down, a three-pass bfloat16 table selection: the upper reading).  The
limits in ``bench/limits.json`` are set between the two.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5
"""
import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    sys.path.insert(0, str(root / "src"))
    from bench import check, harness
    bm = harness.load_benchmark()
    cell, config, traffic, *_ = harness.cell_parts(bm, args.workload)
    lower = {k: 0.0 for k in check.NUMBERS}
    upper = {k: float("inf") for k in check.NUMBERS}
    t = T_START
    for seed in [int(s) for s in args.seeds.split(",")]:
        rec = harness.run_cell(config, traffic, seed=seed,
                               seconds=args.seconds, trace=False,
                               chips=int(cell["chips"]), t_start=t,
                               control=True)
        t = time.perf_counter()
        if not rec["kernel_ok"]:
            raise SystemExit(f"seed {seed}: the kernel did not run compiled")
        ctl = dict(rec["control"], **{k: float("nan") for k in check.EXACT})
        for k in check.NUMBERS:
            lower[k] = max(lower[k], rec["check"][k])
            if k in rec["control"]:
                upper[k] = min(upper[k], rec["control"][k])
        print(json.dumps({"seed": seed, "correct": rec["correct"],
                          "program": rec["check"], "control": ctl,
                          "control_correct": check.verdict(
                              dict(rec["control"],
                                   **{k: 0.0 for k in check.EXACT}),
                              rec["limits"]),
                          "checked": rec["checked"],
                          "check_s": rec["check_s"],
                          "setup_s": rec["setup_s"],
                          "attempted": rec["attempted"],
                          "spilled": rec["spilled"]}), flush=True)
    print(json.dumps({"lower": lower, "upper": upper}), flush=True)


if __name__ == "__main__":
    main()
