"""Drift-recovery benchmark: ACT recovery time after a mid-run demand shift.

At ``shift_at`` the workload's generating suite drifts (see
``repro.apps.workload.make_drifted_suite``): the LLM-heavy small
applications get ``demand_mult``x heavier per-unit demand plus new
self-repeat branch mass, while the arrival rate stays constant — so the
cluster runs contended on ground truth a stale model underestimates.
Three scheduler arms run the SAME deterministic trace:

* ``oracle``    — knowledge base profiled on the *drifted* suite (knows the
  post-shift truth from t=0; the recovery target);
* ``posterior`` — stale knowledge base + online conjugate posterior updates
  (``PosteriorConfig``): completions stream back as Dirichlet branch counts
  and Gamma demand scaling, so Gittins ranks re-learn the shift;
* ``frozen``    — the same stale knowledge base, never updated (pre-PR
  behavior).

Post-shift arrivals are bucketed into ``window_s`` arrival windows; each
arm's ``act_recovery_s`` is the first window start from which its windowed
mean ACT stays within ``(1 + tol)`` of the oracle arm's for every remaining
window (the post-shift horizon when it never settles).  The run FAILS
(exit 1) unless the posterior arm recovers strictly faster than the frozen
arm — the tentpole's dominance contract.  Everything is seeded and
event-driven, so ``act_recovery_s`` is bit-reproducible and the CI trend
gate compares it exactly:

  python scripts/bench_trend.py BENCH_drift.json \
      --baseline benchmarks/baselines/BENCH_drift.smoke.json \
      --field act_recovery_s --direction min --min-ms 0

  PYTHONPATH=src python -m benchmarks.drift [--smoke]
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time

import numpy as np

sys.path.insert(0, "src")  # repo-root invocation without an installed package

from repro.apps.suite import T_IN, T_OUT, build_knowledge_base  # noqa: E402
from repro.apps.workload import (TenantProfile,  # noqa: E402
                                 make_drift_workload, make_drifted_suite)
from repro.core.posterior import PosteriorConfig  # noqa: E402
from repro.serving.simulator import ClusterSim, SimConfig  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402

JSON_PATH = "BENCH_drift.json"

# One tenant submitting the §5.1 mix minus the ten-minute-class apps (DM /
# MRS / LLMR would dominate every window's mean ACT and need hour-long
# traces to average out); the LLM-heavy drift subset is 43% of arrivals.
# rate_per_s keeps the llm slots contended-but-stable before the shift and
# pushed to the edge after it — the regime where a stale model's ordering
# mistakes cost ACT every window.
MIX = {"EV": 0.144, "FEV": 0.144, "CC": 0.144, "ALFWI": 0.144,
       "KBQAV": 0.144, "CG": 0.13, "PE": 0.13}
DRIFT_APPS = ("FEV", "ALFWI", "KBQAV")

FULL = dict(duration_s=600.0, shift_at=120.0, rate_per_s=0.3,
            demand_mult=3.0, p_repeat=0.35, n_llm_slots=8, window_s=60.0,
            tol=0.25, kb_trials=120, seed=11)
SMOKE = dict(duration_s=360.0, shift_at=60.0, rate_per_s=0.3,
             demand_mult=3.0, p_repeat=0.35, n_llm_slots=8, window_s=60.0,
             tol=0.25, kb_trials=120, seed=11)

ARMS = ("oracle", "posterior", "frozen")


def _trace(p):
    return make_drift_workload(
        p["duration_s"], t_in=T_IN, t_out=T_OUT, shift_at=p["shift_at"],
        rate_per_s=p["rate_per_s"], demand_mult=p["demand_mult"],
        p_repeat=p["p_repeat"], drift_apps=DRIFT_APPS,
        n_service_slots=p["n_llm_slots"],
        tenants=[TenantProfile(name="t0", app_mix=MIX)], seed=p["seed"])


def _config(p, arm):
    return SimConfig(
        policy="gittins", seed=5, prewarm_mode="lru",
        n_llm_slots=p["n_llm_slots"], mc_walkers=64,
        posterior=PosteriorConfig() if arm == "posterior" else None)


def _knowledge(p, arm):
    if arm == "oracle":
        drifted = make_drifted_suite(demand_mult=p["demand_mult"],
                                     p_repeat=p["p_repeat"],
                                     drift_apps=DRIFT_APPS)
        return build_knowledge_base(n_trials=p["kb_trials"], seed=3,
                                    apps=drifted)
    return build_knowledge_base(n_trials=p["kb_trials"], seed=3)


def _windowed_act(p, insts, res):
    """Mean ACT of post-shift arrivals, bucketed by arrival-time window
    (window starts are seconds after the shift)."""
    horizon = p["duration_s"] - p["shift_at"]
    n_win = int(np.ceil(horizon / p["window_s"]))
    starts = [i * p["window_s"] for i in range(n_win)]
    sums, counts = [0.0] * n_win, [0] * n_win
    for inst in insts:
        if not inst.app_id.startswith("drift") or inst.app_id not in res.acts:
            continue
        w = min(int((inst.arrival - p["shift_at"]) // p["window_s"]),
                n_win - 1)
        sums[w] += res.acts[inst.app_id]
        counts[w] += 1
    return starts, [s / c if c else float("nan")
                    for s, c in zip(sums, counts)]


def _recovery_s(p, starts, acts, oracle_acts):
    """First window start from which windowed ACT stays within
    (1 + tol) x oracle for every remaining window; the post-shift horizon
    when the arm never settles."""
    horizon = p["duration_s"] - p["shift_at"]
    ok = [not (a > (1.0 + p["tol"]) * o)  # NaN (empty window) passes
          for a, o in zip(acts, oracle_acts)]
    for i, t in enumerate(starts):
        if all(ok[i:]):
            return float(t)
    return float(horizon)


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="short trace for CI (same scenario)")
    ap.add_argument("--out", default=JSON_PATH)
    args = ap.parse_args(argv)
    p = SMOKE if args.smoke else FULL

    insts = _trace(p)
    n_post = sum(1 for i in insts if i.app_id.startswith("drift"))
    print(f"drift trace: {len(insts)} apps ({n_post} post-shift), "
          f"shift at {p['shift_at']:g}s, x{p['demand_mult']:g} demand on "
          f"{'/'.join(DRIFT_APPS)}")

    rows, windowed = [], {}
    for arm in ARMS:
        t0 = time.perf_counter()
        res = ClusterSim(_knowledge(p, arm), _config(p, arm)).run(list(insts))
        wall = time.perf_counter() - t0
        starts, acts = _windowed_act(p, insts, res)
        windowed[arm] = (starts, acts)
        rows.append({
            "name": arm,
            "completed": len(res.acts),
            "mean_act_s": res.mean_act(),
            "post_shift_mean_act_s": float(np.nanmean(acts)),
            "window_starts_s": starts,
            "windowed_act_s": acts,
            "wall_s": wall,
        })
        print(f"{arm:<10} done={rows[-1]['completed']:>3} "
              f"post-shift ACT={rows[-1]['post_shift_mean_act_s']:.1f}s "
              f"windows=[" +
              " ".join(f"{a:.0f}" for a in acts) + f"] ({wall:.1f}s wall)")

    oracle_acts = windowed["oracle"][1]
    for row in rows:
        starts, acts = windowed[row["name"]]
        row["act_recovery_s"] = _recovery_s(p, starts, acts, oracle_acts)

    by_name = {r["name"]: r for r in rows}
    rec_post = by_name["posterior"]["act_recovery_s"]
    rec_frozen = by_name["frozen"]["act_recovery_s"]
    # None (JSON null) when the posterior arm never left the oracle's
    # tolerance band — the ratio is unbounded
    ratio = rec_frozen / rec_post if rec_post > 0 else None
    print(f"recovery: posterior={rec_post:g}s frozen={rec_frozen:g}s "
          f"(frozen/posterior = "
          f"{'inf' if ratio is None else f'{ratio:g}'}x)")

    payload = {
        "benchmark": "drift",
        "smoke": args.smoke,
        "params": dict(p, drift_apps=list(DRIFT_APPS)),
        "platform": f"{platform.system()}-{platform.machine()}",
        "python": platform.python_version(),
        "arms": list(ARMS),
        "recovery_ratio": ratio,
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out} ({len(rows)} rows)")

    if rec_post >= rec_frozen:
        print(f"drift: FAIL — posterior arm did not recover faster than "
              f"frozen ({rec_post:g}s >= {rec_frozen:g}s)")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
