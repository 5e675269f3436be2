"""Prewarm A/B benchmark: does acting on predicted demand pay for itself?

The Hermes claim under test — PDGraph-driven speculative prewarming takes
backend cold starts off the critical path — only means something against a
simulator that actually charges for cold backends.  This benchmark runs the
same workload through the cluster simulator with cold-start latencies
enabled under three backend policies:

  lru      reactive baseline: load on demand, evict least-recently-used
  epwq     CachedAttention-style: prefetch only for queued requests; the
           non-smoke run sweeps its prefetch window (how many upcoming
           trajectory units get prefetched: ``epwq_w2``/``epwq_w4`` arms)
           to probe whether the flat default window is the reason it barely
           helps at this scale
  hermes   the batched device-resident PrewarmPlan riding the fused refresh
           dispatch (per-(app, backend-class) arrival-quantile triggers)

and reports mean/p95 application completion time, cold-start stall seconds,
and the prewarm hit/miss/wasted-warm accounting.  Every run (including
``--smoke``) records machine-readable results in ``BENCH_prewarm.json`` so
CI can archive the trajectory (see docs/BENCHMARKS.md for the schema).

  PYTHONPATH=src python -m benchmarks.prewarm [--smoke] [--paper]
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time

sys.path.insert(0, "src")  # repo-root invocation without an installed package

from benchmarks.common import Csv, kb, workload  # noqa: E402
from repro.serving.simulator import ClusterSim, SimConfig  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402

JSON_PATH = "BENCH_prewarm.json"

ARMS = ("lru", "epwq", "hermes")
# prefetch-window sweep for the flat epwq baseline (non-smoke runs): w=1 is
# the plain `epwq` arm (current-unit-only, CachedAttention-style)
EPWQ_WINDOWS = (2, 4)


def run_arm(knowledge, insts, arm: str, *, seed: int, K: float = 0.5,
            epwq_window: int = 1):
    mode = "epwq" if arm.startswith("epwq") else arm
    cfg = SimConfig(policy="gittins", seed=seed, prewarm_mode=mode, K=K,
                    n_llm_slots=8, mc_walkers=128,
                    kv_capacity=8, lora_capacity=4, dnn_capacity=2,
                    epwq_window=epwq_window)
    t0 = time.perf_counter()
    res = ClusterSim(knowledge, cfg).run(list(insts))
    return res, time.perf_counter() - t0


def run(csv: Csv, paper_scale: bool = False, seed: int = 7,
        smoke: bool = False):
    n, win = (120, 600.0) if paper_scale else (60, 300.0)
    if smoke:
        n, win = 10, 120.0
    knowledge = kb()
    insts = workload(n, win, seed=seed)
    arms = [(a, 1) for a in ARMS]
    if not smoke:   # 3 window values total: epwq (w=1) + the sweep arms
        arms[2:2] = [(f"epwq_w{w}", w) for w in EPWQ_WINDOWS]
    records = []
    base = None
    for arm, w in arms:
        res, wall = run_arm(knowledge, insts, arm, seed=seed, epwq_window=w)
        if arm == "lru":
            base = res
        p = res.prewarm_stats
        red = 100 * (1 - res.mean_act() / base.mean_act())
        row = {
            "arm": arm, "apps": n, "mean_act_s": res.mean_act(),
            "epwq_window": w if arm.startswith("epwq") else None,
            "p95_act_s": res.p95_act(),
            "act_reduction_vs_lru_pct": red,
            "coldstart_stall_s": p["coldstart_stall_s"],
            "coldstart_events": p["coldstart_events"],
            "prewarm_pushed": p["prewarm_pushed"],
            "spec_loads": p["spec_loads"], "spec_used": p["spec_used"],
            "wasted_warm_s": p["wasted_warm_s"],
            "hits": p["hits"], "misses": p["misses"],
            "bench_wall_s": wall,
        }
        records.append(row)
        csv.add(f"prewarm/{arm}/apps={n}", 0.0,
                f"mean_act={res.mean_act():.1f}s "
                f"reduction={red:.1f}% "
                f"stall={p['coldstart_stall_s']:.0f}s "
                f"spec_used={p['spec_used']:.0f}/{p['spec_loads']:.0f} "
                f"wasted_warm={p['wasted_warm_s']:.0f}s")
    payload = {
        "benchmark": "prewarm",
        "smoke": smoke,
        "apps": n, "window_s": win,
        "platform": platform.platform(),
        "rows": records,
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
    ap.add_argument("--paper", action="store_true",
                    help="paper-scale workload")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    csv = Csv()
    run(csv, paper_scale=args.paper, seed=args.seed, smoke=args.smoke)
    csv.dump()


if __name__ == "__main__":
    main()
