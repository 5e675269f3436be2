"""Overload-survival benchmark: goodput vs offered load under flash crowds.

Three serving arms run the SAME deterministic flash-crowd traces at a sweep
of offered-load points (spike multiplier x base load inside the spike
window):

* ``hermes_shed``  — hermes_ddl triage + SLO-class admission/shedding with
  per-tenant fairness + hysteresis degradation (the PR-7 overload stack);
* ``hermes_naive`` — hermes_ddl triage alone: hopeless work parks at the
  back of the queue but is never shed (pre-PR-7 behavior);
* ``edf``          — earliest-deadline-first baseline.

Per (load point, arm) the record carries ``goodput_per_s`` (SLO-attaining
completions per second of makespan — the metric shedding is graded on),
``goodput_service_s`` (useful service seconds delivered per second),
SLO-attainment overall and per class, and the shed/completion counts.
Everything is seeded and event-driven — goodput is bit-reproducible, so
the CI trend gate compares it exactly:

  python scripts/bench_trend.py BENCH_overload.json \
      --baseline benchmarks/baselines/BENCH_overload.smoke.json \
      --field goodput_per_s --direction max --min-ms 0

The sweep is followed by a **fault-injection canary**: the shedding arm
re-runs one overloaded point with a crash + staggered recovery plan in the
LLM pool, asserting the at-least-once contract — every non-shed
application completes, no unit is lost or double-counted, and each orphan
was re-queued exactly once.  A violation exits non-zero (the CI smoke leg
runs this benchmark, so the canary gates merges).

  PYTHONPATH=src python -m benchmarks.overload [--smoke]
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time

sys.path.insert(0, "src")  # repo-root invocation without an installed package

from benchmarks.common import kb  # noqa: E402
from repro.apps.suite import T_IN, T_OUT  # noqa: E402
from repro.apps.workload import make_flash_crowd_workload  # noqa: E402
from repro.core.admission import AdmissionConfig, DegradeConfig  # noqa: E402
from repro.runtime.fault_tolerance import FaultEvent  # noqa: E402
from repro.serving.backends import FaultConfig  # noqa: E402
from repro.serving.simulator import ClusterSim, SimConfig  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402

JSON_PATH = "BENCH_overload.json"

# Load points are spike multipliers: offered load inside the spike window
# is mult x base_load, so 1.0 is the busy-but-stable operating point and
# everything past ~1.25/base_load is overloaded.  The sweep's overloaded
# points are where the shedding arm must dominate the naive arm (smoke is
# the same scenario, shorter trace + fewer points, feeding the CI gate).
FULL = dict(duration_s=240.0, base_load=0.8, spike_start=30.0,
            spike_dur=80.0, n_llm_slots=8, seed=6, kb_trials=120,
            mults=(1.0, 15.0, 20.0, 25.0))
SMOKE = dict(duration_s=240.0, base_load=0.8, spike_start=30.0,
             spike_dur=80.0, n_llm_slots=8, seed=6, kb_trials=120,
             mults=(1.0, 15.0, 20.0))

ARMS = ("hermes_shed", "hermes_naive", "edf")


def _trace(p, mult):
    return make_flash_crowd_workload(
        p["duration_s"], t_in=T_IN, t_out=T_OUT, base_load=p["base_load"],
        spike_mult=mult, spike_start=p["spike_start"],
        spike_dur=p["spike_dur"], n_service_slots=p["n_llm_slots"],
        with_deadlines=True, seed=p["seed"])


def _config(p, arm, faults=None):
    kw = dict(policy="hermes_ddl", seed=5, prewarm_mode="lru",
              n_llm_slots=p["n_llm_slots"], mc_walkers=64, faults=faults)
    if arm == "edf":
        kw["policy"] = "edf"
    elif arm == "hermes_shed":
        kw["admission"] = AdmissionConfig(pressure_watermark=1.0)
        kw["degrade"] = DegradeConfig(high_watermark=2.0, low_watermark=0.5,
                                      llm_speedup=2.0)
    return SimConfig(**kw)


def _row(name, mult, p, insts, res, wall):
    return {
        "name": name,
        "spike_mult": mult,
        "offered_load": mult * p["base_load"],
        "n_offered": len(insts),
        "completed": len(res.acts),
        "shed": len(res.shed),
        "makespan_s": res.makespan,
        "goodput_per_s": res.goodput(),
        "goodput_service_s": res.goodput_service_s(),
        "slo_attainment": res.slo_attainment(),
        "slo_attainment_standard": res.slo_attainment("standard"),
        "slo_attainment_best_effort": res.slo_attainment("best_effort"),
        "degraded_units": res.degrade_stats.get("degraded_units", 0.0),
        "wall_s": wall,
    }


def _fault_canary(p, knowledge):
    """One overloaded point with a crash mid-spike and a staggered
    recovery: the at-least-once contract must hold exactly."""
    mult = p["mults"][-1]
    insts = _trace(p, mult)
    faults = FaultConfig(
        events=(FaultEvent(t=p["spike_start"] + 20.0, kind="crash",
                           pool="llm", backend=1),
                FaultEvent(t=p["spike_start"] + 50.0, kind="recover",
                           pool="llm", backend=1)),
        n_backends=(("llm", 4),), heartbeat_timeout_s=1.0)
    sim = ClusterSim(knowledge, _config(p, "hermes_shed", faults=faults))
    res = sim.run(list(insts))
    by_id = {i.app_id: i for i in insts}
    offered = set(by_id)
    done, shed = set(res.acts), set(res.shed)
    problems = []
    if res.fault_stats.get("crashes", 0) < 1:
        problems.append("no crash was injected")
    if done | shed != offered or done & shed:
        problems.append("apps lost or double-terminal "
                        f"(done={len(done)} shed={len(shed)} "
                        f"offered={len(offered)})")
    if sorted(res.completion_order) != sorted(done) or \
            len(set(res.completion_order)) != len(res.completion_order):
        problems.append("completion order double-counts an app")
    short = [a for a in done
             if res.units_done[a] != len(by_id[a].trajectory)]
    if short:
        problems.append(f"{len(short)} apps completed with missing units")
    if res.fault_stats.get("requeued", 0) != \
            res.fault_stats.get("orphaned", 0):
        problems.append("orphan/requeue counts diverge")
    return {
        "spike_mult": mult,
        "crashes": res.fault_stats.get("crashes", 0.0),
        "orphaned": res.fault_stats.get("orphaned", 0.0),
        "requeued": res.fault_stats.get("requeued", 0.0),
        "lost_service_s": res.fault_stats.get("lost_service_s", 0.0),
        "completed": len(done),
        "shed": len(shed),
        "ok": not problems,
        "problems": problems,
    }


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="short sweep for CI (same scenario, fewer points)")
    ap.add_argument("--out", default=JSON_PATH)
    args = ap.parse_args(argv)
    p = SMOKE if args.smoke else FULL

    knowledge = kb(p["kb_trials"])
    rows = []
    for mult in p["mults"]:
        insts = _trace(p, mult)
        for arm in ARMS:
            t0 = time.perf_counter()
            res = ClusterSim(knowledge, _config(p, arm)).run(list(insts))
            wall = time.perf_counter() - t0
            name = f"flash_x{mult:g}/{arm}"
            rows.append(_row(name, mult, p, insts, res, wall))
            r = rows[-1]
            print(f"{name:<28} offered={r['offered_load']:>4.1f} "
                  f"done={r['completed']:>3} shed={r['shed']:>3} "
                  f"goodput={r['goodput_per_s']:.4f}/s "
                  f"slo={r['slo_attainment']:.2f} ({wall:.1f}s wall)")

    # the PR's dominance contract, checked on every run: at every
    # overloaded point the shedding arm's goodput >= the naive arm's
    by_name = {r["name"]: r for r in rows}
    violations = []
    for mult in p["mults"]:
        if mult * p["base_load"] <= 1.0:
            continue
        g_shed = by_name[f"flash_x{mult:g}/hermes_shed"]["goodput_per_s"]
        g_naive = by_name[f"flash_x{mult:g}/hermes_naive"]["goodput_per_s"]
        if g_shed < g_naive:
            violations.append(f"x{mult:g}: shed {g_shed:.4f} < "
                              f"naive {g_naive:.4f}")

    canary = _fault_canary(p, knowledge)
    print(f"fault canary: crashes={canary['crashes']:g} "
          f"orphaned={canary['orphaned']:g} requeued={canary['requeued']:g} "
          f"ok={canary['ok']}")

    payload = {
        "benchmark": "overload",
        "smoke": args.smoke,
        "params": {k: (list(v) if isinstance(v, tuple) else v)
                   for k, v in p.items()},
        "platform": f"{platform.system()}-{platform.machine()}",
        "python": platform.python_version(),
        "arms": list(ARMS),
        "rows": rows,
        "fault_canary": canary,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out} ({len(rows)} rows)")

    if violations:
        print("overload: FAIL — shedding lost to naive at overloaded "
              "points:\n  " + "\n  ".join(violations))
        return 1
    if not canary["ok"]:
        print("overload: FAIL — fault canary violated the at-least-once "
              "contract:\n  " + "\n  ".join(canary["problems"]))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
