#!/usr/bin/env python3
"""Run one cell with the profiler on and print what its trace holds:
planes, lines, event counts, the most frequent and the longest event
names of each device line, and a few events' stats.  For reading a trace
by hand before writing a reduction against it.

    python3 bench/inspect_trace.py --workload <cell> --seed <n> --seconds <s>
"""
import argparse
import collections
import glob
import sys
import time
from pathlib import Path

T_START = time.perf_counter()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    sys.path.insert(0, str(root / "src"))
    from bench import harness
    bm = harness.load_benchmark()
    cell, config, traffic, *_ = harness.cell_parts(bm, args.workload)
    tdir = root / ".bench_trace" / ("inspect-" + args.workload)
    rec = harness.run_cell(config, traffic, seed=args.seed,
                           seconds=args.seconds, trace=True,
                           t_start=T_START, trace_dir=tdir)
    from jax.profiler import ProfileData
    path = sorted(glob.glob(str(tdir / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    print(f"trace file {path}: {Path(path).stat().st_size} bytes")
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            t0 = min(e.start_ns for e in evs)
            t1 = max(e.end_ns for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events, "
                  f"{t0:.0f}..{t1:.0f} ns")
            if plane.name.startswith("/host"):
                names = collections.Counter(e.name for e in evs)
                print("    top names:", names.most_common(8))
                continue
            dur = collections.Counter()
            cnt = collections.Counter()
            for e in evs:
                dur[e.name] += e.duration_ns
                cnt[e.name] += 1
            for name, d in dur.most_common(12):
                print(f"    {name!r}: {cnt[name]} events, {d / 1e6:.3f} ms")
            for e in evs[:3]:
                print(f"    stats of {e.name!r}: "
                      f"{[(k, str(v)[:80]) for k, v in e.stats][:12]}")
    tr = rec["trace"]
    print("reduced:", {k: tr[k] for k in ("window_s", "busy_s", "n_ops")})
    print("breakdown:", tr["breakdown"])
    print("check:", rec["check"], "correct:", rec["correct"])


if __name__ == "__main__":
    main()
