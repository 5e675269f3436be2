"""Benchmark trend gate: fail CI when a seeded benchmark record regresses.

Compares a fresh benchmark record (``BENCH_overload.json``,
``BENCH_drift.json``: each run of their benchmark writes one, including
``--smoke``) against a committed baseline record and exits non-zero when
any row present in BOTH files regressed by more than ``--max-regress-pct``
in the gated field.

Honesty guards (cross-machine timing comparisons lie — see
docs/BENCHMARKS.md):

* arms whose baseline tick is below ``--min-ms`` are skipped — at smoke
  scale a sub-millisecond tick is jitter, not signal;
* when the baseline was recorded on a different platform string the gate
  downgrades to a warning (exit 0) unless ``--force`` — a laptop baseline
  must not fail a CI runner and vice versa;
* rows are matched by exact record name, so new arms/sizes pass until a
  baseline containing them is committed;
* each row compares the ``ms_per_tick_min`` (min-of-N) estimator, and
  ``--update`` folds a fresh record into the baseline as a per-row MAX —
  the baseline is the upper envelope of healthy runs, so one lucky fast
  draw can never poison it into flagging every later run;
* ``--field``/``--direction`` generalize the gate beyond latency: the
  overload benchmark gates ``--field goodput_per_s --direction max``
  (larger is better — regression = shrinkage, the envelope folds as a
  per-row MIN, and ``--min-ms 0`` keeps sub-1.0 goodput rows in play);
* ``--require ARM`` (repeatable) closes the new-arm blind spot of
  name-matching: the fresh record must contain at least one row whose
  ``arm`` field equals each required name, with the gated field present —
  an arm that silently stops being measured (skipped, renamed, crashed)
  fails the gate even though no shared row regressed.

Usage:
  python scripts/bench_trend.py BENCH_overload.json \
      --baseline benchmarks/baselines/BENCH_overload.smoke.json \
      --field goodput_per_s --direction max --min-ms 0 --force
  # refresh the baseline (run the benchmark a few times, folding each in):
  python scripts/bench_trend.py BENCH_overload.json --update \
      --baseline benchmarks/baselines/BENCH_overload.smoke.json \
      --field goodput_per_s --direction max --min-ms 0 --force

Stdlib-only (runs in the CI canary step before any install caching).
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys


def row_value(r: dict, field: str):
    # the noise-robust min-of-N estimator when recorded ("<field>_min");
    # the plain field for records predating it (or deterministic metrics
    # like goodput that need no envelope estimator)
    v = r.get(field + "_min", r.get(field))
    return None if v is None else float(v)


def load_rows(path: str, field: str) -> dict:
    with open(path) as f:
        payload = json.load(f)
    rows = {}
    for r in payload["rows"]:
        v = row_value(r, field)
        if v is not None:          # rows without the field pass untouched
            rows[r["name"]] = v
    return payload, rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("fresh", help="freshly written benchmark record")
    ap.add_argument("--baseline", required=True,
                    help="committed baseline record to compare against")
    ap.add_argument("--max-regress-pct", type=float, default=25.0,
                    help="fail when ms/tick grows more than this (%%)")
    ap.add_argument("--min-ms", type=float, default=1.0,
                    help="skip arms whose baseline value is below this")
    ap.add_argument("--field", default="ms_per_tick",
                    help="row field to gate on (a '<field>_min' estimator "
                         "is preferred when recorded)")
    ap.add_argument("--direction", choices=("min", "max"), default="min",
                    help="'min': smaller is better (latency; regression = "
                         "growth, baseline folds as an upper envelope); "
                         "'max': larger is better (goodput; regression = "
                         "shrinkage, baseline folds as a lower envelope)")
    ap.add_argument("--require", action="append", default=[],
                    metavar="ARM",
                    help="fail unless the fresh record has a row with this "
                         "'arm' field carrying the gated field (repeatable)")
    ap.add_argument("--force", action="store_true",
                    help="fail even across differing platform strings")
    ap.add_argument("--update", action="store_true",
                    help="fold the fresh record into the baseline "
                         "(per-row max of the min estimators; copies "
                         "verbatim when no baseline exists)")
    args = ap.parse_args(argv)

    if args.update:
        try:
            with open(args.baseline) as f:
                base_payload = json.load(f)
        except FileNotFoundError:
            shutil.copyfile(args.fresh, args.baseline)
            print(f"baseline created: {args.fresh} -> {args.baseline}")
            return 0
        with open(args.fresh) as f:
            fresh_payload = json.load(f)
        # the BASELINE payload stays the carrier: folding rows in must not
        # rewrite its platform string (that would mix machines in one
        # envelope and silently disarm the platform-match gate below)
        if fresh_payload.get("platform") != base_payload.get("platform") \
                and not args.force:
            print("bench_trend: refusing to fold a "
                  f"{fresh_payload.get('platform')!r} run into a "
                  f"{base_payload.get('platform')!r} baseline "
                  "(--force to restart the envelope on this machine)")
            return 1
        if fresh_payload.get("platform") != base_payload.get("platform"):
            shutil.copyfile(args.fresh, args.baseline)   # --force: restart
            print(f"baseline restarted on this platform: {args.baseline}")
            return 0
        by_name = {r["name"]: r for r in base_payload["rows"]}
        # per-fold growth cap at half the gate threshold: the envelope may
        # absorb noise peaks, but a sequence of sub-threshold regressions
        # must not ratchet it upward unbounded (slow drift stays visible
        # against the intentionally-refreshed committed baseline)
        cap = args.max_regress_pct / 200.0
        for r in fresh_payload["rows"]:
            prev = by_name.get(r["name"])
            if prev is None:
                by_name[r["name"]] = r
                continue
            pv, fv = row_value(prev, args.field), row_value(r, args.field)
            if pv is None or fv is None:
                by_name[r["name"]] = r
                continue
            worse = fv > pv if args.direction == "min" else fv < pv
            if worse:
                r = dict(r)
                folded = min(fv, pv * (1.0 + cap)) \
                    if args.direction == "min" else max(fv, pv * (1.0 - cap))
                key = args.field + "_min" if args.field + "_min" in r \
                    else args.field
                r[key] = folded
                by_name[r["name"]] = r
        base_payload["rows"] = [by_name[k] for k in sorted(by_name)]
        with open(args.baseline, "w") as f:
            json.dump(base_payload, f, indent=2)
        print(f"baseline envelope updated: {args.baseline} "
              f"({len(by_name)} rows)")
        return 0

    fresh_payload, fresh = load_rows(args.fresh, args.field)
    base_payload, base = load_rows(args.baseline, args.field)

    if args.require:
        have = {r.get("arm") for r in fresh_payload["rows"]
                if row_value(r, args.field) is not None}
        missing = [a for a in args.require if a not in have]
        if missing:
            print(f"bench_trend: FAIL — required arm(s) missing from "
                  f"{args.fresh}: {missing} (measured: {sorted(have)})")
            return 1

    cross = fresh_payload.get("platform") != base_payload.get("platform")
    shared = sorted(set(fresh) & set(base))
    if not shared:
        print("bench_trend: no shared rows between fresh and baseline; "
              "commit a fresh baseline (--update)")
        return 0

    regressions = []
    print(f"{'row':<52} {'base':>9} {'fresh':>9} {'delta':>8}")
    for name in shared:
        b, f = base[name], fresh[name]
        if b < args.min_ms or b <= 0.0:
            continue
        # positive pct always means "worse by pct" in the gated direction
        pct = 100.0 * (f - b) / b
        if args.direction == "max":
            pct = -pct
        flag = " <-- REGRESSION" if pct > args.max_regress_pct else ""
        unit = "ms" if args.field.startswith("ms") else ""
        print(f"{name:<52} {b:>7.4g}{unit} {f:>7.4g}{unit} "
              f"{pct:>+7.1f}%{flag}")
        if pct > args.max_regress_pct:
            regressions.append((name, b, f, pct))

    if regressions and cross and not args.force:
        print(f"\nbench_trend: {len(regressions)} regression(s) but the "
              "baseline was recorded on a different platform "
              f"({base_payload.get('platform')!r} vs "
              f"{fresh_payload.get('platform')!r}); warning only "
              "(--force to fail anyway)")
        return 0
    if regressions:
        print(f"\nbench_trend: FAIL — {len(regressions)} arm(s) regressed "
              f"more than {args.max_regress_pct:.0f}% vs {args.baseline}")
        return 1
    print("\nbench_trend: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
