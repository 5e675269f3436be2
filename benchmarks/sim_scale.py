"""Event-engine scale benchmark: calendar (array-native) vs heap simulator.

The claim behind the array-native engine (``SimConfig.engine="calendar"``):
a 100k-concurrent-application open-arrival trace — millions of scheduler
events — runs in minutes of wall time, where the seed's heap engine spends
its time in per-event Python tuple churn and per-tick O(queue) rank/key
rebuilds.  This benchmark measures both engines on the SAME overloaded
open-arrival trace:

* the **calendar** arm runs the trace to completion, sampling wall clock vs
  queue size (live applications, waiting tasks) every checkpoint;
* the **heap** arm is event-capped (``heap_event_cap``): running the seed
  engine to completion at this scale would take hours, so it processes the
  same FIRST ``heap_event_cap`` events of the trace — deep enough that its
  last checkpoint window sits in the 100k-live-app regime.

Two ratios come out, like-for-like by construction (bit-equivalent engines
drain identical micro-batches, so checkpoints align on event counts):

* ``speedup_same_prefix`` — wall clock over the identical event prefix
  (diluted by the cheap small-queue warm-up ramp);
* ``speedup_at_depth`` (headline) — events/sec inside the deepest common
  checkpoint window, i.e. the sustained rate at the 100k-concurrent-app
  operating point where the heap engine's per-tick O(live + waiting)
  rank/key rebuilds dominate.

The trace uses ``policy="fcfs_app"`` (a ``view_free`` policy: ranks come
from AppRuntime fields with no MC demand estimation, so the benchmark
isolates the host event engine rather than the refresh backbone — and the
heap arm stays measurable), ``preemptive=False`` and ``prewarm_mode="lru"``.
Engine bit-equivalence at this scale is pinned separately by
``tests/test_sim_engine.py``; the smoke configuration re-checks it here as
a drift canary.

Every run (including ``--smoke``) writes ``BENCH_sim_scale.json``; smoke
rows feed the CI trend gate against
``benchmarks/baselines/BENCH_sim_scale.smoke.json`` (the gate compares the
``ms_per_tick_min`` field, which for this benchmark carries milliseconds
per 1k events — the same monotone "smaller is better" contract).

  PYTHONPATH=src python -m benchmarks.sim_scale [--smoke]
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time
import warnings

sys.path.insert(0, "src")  # repo-root invocation without an installed package

from benchmarks.common import Csv, kb  # noqa: E402
from repro.apps.suite import T_IN, T_OUT  # noqa: E402
from repro.apps.workload import make_open_workload  # noqa: E402
from repro.serving.simulator import ClusterSim, SimConfig  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402

JSON_PATH = "BENCH_sim_scale.json"

# full scale: a heavily overloaded open-arrival trace (the nominal load is
# solved against the LLM pool alone, and docker/dnn pools add capacity, so
# saturating the cluster takes a nominal rho well past 1) — the backlog
# climbs through 100k+ concurrent live applications mid-run.  The heap arm
# is event-capped deep enough that its LAST checkpoint window sits in the
# 100k-live regime, where its per-tick O(live + waiting) rebuilds dominate.
FULL = dict(n_apps=150_000, duration_s=900.0, target_load=10.0,
            n_llm_slots=1024, n_docker_slots=2048, n_dnn_slots=128,
            heap_event_cap=400_000, checkpoint_every=20_000)
SMOKE = dict(n_apps=3000, duration_s=90.0, target_load=6.0,
             n_llm_slots=512, n_docker_slots=1024, n_dnn_slots=64,
             heap_event_cap=None, checkpoint_every=500)


def _trace(p, seed):
    return make_open_workload(
        p["duration_s"], t_in=T_IN, t_out=T_OUT,
        target_load=p["target_load"], n_service_slots=p["n_llm_slots"],
        process="gamma", cv=2.5, tenants=16, seed=seed,
        max_apps=p["n_apps"])


def _config(p, engine, seed):
    # refine=False: online demand conditioning feeds rank/prewarm views a
    # view_free policy never reads — dead per-transition work for BOTH arms.
    # The heap arm is the benchmark's intended deprecated-engine baseline,
    # so its construction warning is suppressed here.
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return SimConfig(policy="fcfs_app", preemptive=False, refine=False,
                         prewarm_mode="lru", engine=engine, seed=seed,
                         n_llm_slots=p["n_llm_slots"],
                         n_docker_slots=p["n_docker_slots"],
                         n_dnn_slots=p["n_dnn_slots"],
                         kv_capacity=4 * p["n_llm_slots"],
                         lora_capacity=2 * p["n_llm_slots"],
                         docker_capacity=p["n_docker_slots"],
                         dnn_capacity=p["n_dnn_slots"],
                         mc_walkers=16)


def _run_arm(knowledge, insts, p, engine, seed, max_events=None):
    """Run one engine over the trace, sampling (events, wall, live apps,
    waiting tasks) checkpoints.  Returns (result, record)."""
    sim = ClusterSim(knowledge, _config(p, engine, seed))
    every = p["checkpoint_every"]
    checkpoints = []
    t0 = time.perf_counter()

    def sample(s):
        if s.events_processed // every > len(checkpoints):
            checkpoints.append({
                "events": s.events_processed,
                "wall_s": time.perf_counter() - t0,
                "live_apps": len(s.sched._live),
                "waiting_tasks": int(sum(len(w)
                                         for w in s.waiting.values())),
            })

    res = sim.run(insts, max_events=max_events, progress=sample)
    wall = time.perf_counter() - t0
    events = sim.events_processed
    peak_live = max([c["live_apps"] for c in checkpoints],
                    default=len(sim.sched._live))
    rec = {
        "engine": engine, "apps": len(insts), "events": events,
        "wall_s": wall, "events_per_sec": events / max(wall, 1e-9),
        "peak_live_apps": int(peak_live),
        "completed_apps": len(res.acts),
        "makespan_s": res.makespan,
        "capped": max_events is not None,
        "checkpoints": checkpoints,
    }
    return res, rec


def _wall_at(checkpoints, events, fallback):
    """Wall clock when the run crossed ``events`` (first checkpoint past
    it); the like-for-like numerator/denominator of the prefix ratio."""
    for c in checkpoints:
        if c["events"] >= events:
            return c["wall_s"]
    return fallback


def _window_rate(checkpoints, i):
    """events/sec inside checkpoint window ``i`` (between checkpoints i-1
    and i; i=0 measures from the start of the run).  Engine checkpoints
    align exactly — bit-equivalent engines drain identical micro-batches,
    so the i-th checkpoint of both arms sits on the same event count."""
    c = checkpoints[i]
    e0 = checkpoints[i - 1]["events"] if i else 0
    w0 = checkpoints[i - 1]["wall_s"] if i else 0.0
    return (c["events"] - e0) / max(c["wall_s"] - w0, 1e-9)


def run(csv: Csv, smoke: bool = False, seed: int = 7):
    p = SMOKE if smoke else FULL
    knowledge = kb(60 if smoke else 200)
    insts = _trace(p, seed)
    print(f"# trace: {len(insts)} applications over {p['duration_s']}s")

    res_cal, rec_cal = _run_arm(knowledge, insts, p, "calendar", seed)
    cap = p["heap_event_cap"]
    res_heap, rec_heap = _run_arm(knowledge, insts, p, "heap", seed,
                                  max_events=cap)

    if smoke:
        # drift canary: full-run equivalence at smoke scale (the real
        # contract lives in tests/test_sim_engine.py)
        assert res_cal.completion_order == res_heap.completion_order
        assert res_cal.acts == res_heap.acts

    # whole-prefix ratio: wall over the identical event prefix both engines
    # processed (diluted by the cheap small-queue start of the trace)
    prefix = rec_heap["events"]
    cal_prefix_wall = _wall_at(rec_cal["checkpoints"], prefix,
                               rec_cal["wall_s"])
    speedup = rec_heap["wall_s"] / max(cal_prefix_wall, 1e-9)

    # headline: events/sec at the deepest operating point both arms share —
    # the heap arm's LAST checkpoint window (100k+ live apps at full scale).
    # This is the sustained-rate claim: what each engine does per second
    # once the queues are at scale, not amortized over the warm-up ramp.
    deep_i = min(len(rec_heap["checkpoints"]),
                 len(rec_cal["checkpoints"])) - 1
    if deep_i >= 0:
        deep_cal = _window_rate(rec_cal["checkpoints"], deep_i)
        deep_heap = _window_rate(rec_heap["checkpoints"], deep_i)
        deep_live = rec_heap["checkpoints"][deep_i]["live_apps"]
        deep_speedup = deep_cal / max(deep_heap, 1e-9)
    else:                     # trace too small for one full window
        deep_cal = rec_cal["events_per_sec"]
        deep_heap = rec_heap["events_per_sec"]
        deep_live = rec_cal["peak_live_apps"]
        deep_speedup = deep_cal / max(deep_heap, 1e-9)

    rows = []
    for rec in (rec_cal, rec_heap):
        n = rec["apps"]
        name = f"sim_scale/{rec['engine']}/apps={n}"
        ms_per_kevent = 1e6 * rec["wall_s"] / max(rec["events"], 1)
        csv.add(name, 1e3 * ms_per_kevent,
                f"{rec['events_per_sec']:,.0f} events/s "
                f"peak_live={rec['peak_live_apps']:,}"
                + (" (event-capped)" if rec["capped"] else ""))
        rows.append({"name": name, **rec,
                     # the trend gate compares ms_per_tick_min: here it
                     # carries ms per 1k drained events (same smaller-is-
                     # better contract as the refresh benchmark's tick)
                     "ms_per_tick": ms_per_kevent,
                     "ms_per_tick_min": ms_per_kevent})
    csv.add("sim_scale/speedup_same_prefix", speedup,
            f"calendar {speedup:.1f}x faster over first {prefix:,} events")
    csv.add("sim_scale/speedup_at_depth", deep_speedup,
            f"calendar {deep_cal:,.0f} vs heap {deep_heap:,.0f} events/s "
            f"at {deep_live:,} live apps")

    payload = {
        "benchmark": "sim_scale",
        "smoke": smoke,
        "params": {k: v for k, v in p.items()},
        "policy": "fcfs_app",
        "platform": platform.platform(),
        "rows": rows,
        "speedup": {
            "calendar_vs_heap_same_prefix": speedup,
            "prefix_events": prefix,
            "calendar_events_per_sec": rec_cal["events_per_sec"],
            "heap_events_per_sec": rec_heap["events_per_sec"],
            "calendar_vs_heap_at_depth": deep_speedup,
            "depth_live_apps": int(deep_live),
            "depth_calendar_events_per_sec": deep_cal,
            "depth_heap_events_per_sec": deep_heap,
        },
    }
    with open(JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {JSON_PATH}")
    return payload


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration (API drift canary)")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    csv = Csv()
    run(csv, smoke=args.smoke, seed=args.seed)
    csv.dump()


if __name__ == "__main__":
    main()
