"""Refresh-tick microbenchmark: looped vs composed vs fused priority refresh.

The Fig. 15 argument — scheduling overhead stays negligible at cluster
scale — only holds if the bucket-tick refresh is a batched hot path.  This
benchmark builds a queue of N live applications and times one full refresh
tick (re-draw every demand estimate from the PDGraphs, re-bucketize,
re-rank) under:

  looped        the seed implementation — one MC walk + one histogram per
                application per tick (``HermesScheduler(refresh=RefreshConfig(mode="looped"))``)
  composed      PR 1: one jitted vmapped walk, host-side numpy bucketize,
                second jitted rank dispatch (``RefreshConfig(mode="composed")``)
  fused         the device-resident pipeline with the threefry walker —
                walk → bucketize → rank in ONE dispatch, bit-identical
                demand samples to composed (``RefreshConfig(mode="fused",
                walker="threefry")``): isolates the fusion gain
  fused_pallas  the PR-4 fused path: the counter-RNG ``pdgraph_walk``
                kernel package with phase compaction (``walker="pallas"``,
                pinned ``rank_in_kernel=False`` — the legacy
                walk -> histogram -> rank composition, kept as the A/B
                reference; Pallas kernel on TPU, its bit-identical jnp twin
                on CPU): fusion + RNG + compaction gains together
  fused_rank    the shipping one-pass configuration (ISSUE 9 defaults):
                ``pdgraph_walk_ranked`` carries each walker block from
                transition sampling to per-app histogram rows and Gittins
                ranks in ONE dispatch — VMEM-resident on TPU (no (A, W)
                totals round-trip), the lossless 16-bit quantized twin with
                the lane-gated multi-stage compaction schedule on CPU.
                Bit-identical ranks to fused_pallas
  fused_delta   the dirty-set delta refresh over the persistent slot store
                (``mode="fused_delta"``, the default): before each tick a realistic
                fraction (DIRTY_FRAC) of the queue takes a unit-transition
                event; the tick re-walks ONLY those slots and re-ranks the
                whole arena in place from persisted device histograms —
                the incremental-re-estimation claim, measured
  fused_delta_mesh1    the PR-5 mesh-sharded pipeline on a degenerate
                one-device mesh: same delta semantics, but stale-row-only
                ranking, packed-carrier dispatch and multi-stage walk
                compaction — the 1-shard scaling baseline of the mesh
  fused_delta_sharded  the mesh pipeline with the slot arena partitioned
                across min(8, device_count) devices via shard_map; one
                dispatch per tick walks each shard's dirty rows locally.
                Skipped on single-device runs — this module forces
                XLA_FLAGS=--xla_force_host_platform_device_count=8 when run
                directly (before jax loads), so the CPU arm exercises a
                real 8-way mesh; bit-identical ranks to fused_delta for
                the same placement
  fused_delta_skewed    the sharded pipeline fed a worst-case dirty set —
                every dirty slot lands on ONE shard (residue placement), so
                one shard walks everything while the rest idle: the
                measured dirty-imbalance straggler gap vs the uniform
                fused_delta_sharded arm
  fused_delta_balanced  the same skewed dirty set with walker-lane
                balancing ON (``lane_balance=0.25``): past the imbalance
                threshold the tick redistributes walker lanes round-robin
                across shards and all-gathers the packed result rows back
                to their owners — one collective buys back the straggler
                gap.  Bit-identical ranks to the unbalanced tick

plus the cheaper rank-only tick (demand estimates cached, re-rank only).

Every run (including ``--smoke``) also records machine-readable results in
``BENCH_refresh_tick.json`` so CI can archive the trajectory.

  PYTHONPATH=src python -m benchmarks.refresh_tick [--smoke] [--paper]
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time
from typing import Tuple

import numpy as np

sys.path.insert(0, "src")  # repo-root invocation without an installed package

# a CPU mesh needs forced host devices BEFORE jax initializes; when another
# harness (benchmarks.run) imported jax first this is a silent no-op and the
# sharded arm simply skips
if "jax" not in sys.modules and \
        "force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", "") and \
        not os.environ.get("REFRESH_TICK_NO_MESH"):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

from benchmarks.common import Csv, kb  # noqa: E402
from repro.apps.suite import T_IN, T_OUT  # noqa: E402
from repro.core.refresh_config import RefreshConfig  # noqa: E402
from repro.core.scheduler import HermesScheduler  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402

MC_WALKERS = 128
JSON_PATH = "BENCH_refresh_tick.json"
# largest power of two <= device count (capped at 8): RefreshMesh requires a
# pow2 shard count, and hosts can expose e.g. 6 accelerators
MESH_SHARDS = 1 << (min(8, jax.device_count()).bit_length() - 1)

# prewarm=False isolates the rank-refresh cost (comparable across PRs);
# fused_prewarm measures the increment of computing the batched prewarm
# trigger matrix inside the same dispatch (arrival tracking + reduction)
ARMS = {
    "looped": dict(refresh=RefreshConfig(mode="looped"), prewarm=False),
    "composed": dict(refresh=RefreshConfig(mode="composed"), prewarm=False),
    "fused": dict(refresh=RefreshConfig(mode="fused", walker="threefry"),
                  prewarm=False),
    "fused_pallas": dict(refresh=RefreshConfig(mode="fused",
                                               rank_in_kernel=False),
                         prewarm=False),
    "fused_rank": dict(refresh=RefreshConfig(mode="fused"), prewarm=False),
    "fused_prewarm": dict(refresh=RefreshConfig(mode="fused"), prewarm=True),
    "fused_delta": dict(refresh=RefreshConfig(), prewarm=False),
    "fused_delta_prewarm": dict(refresh=RefreshConfig(), prewarm=True),
    "fused_delta_mesh1": dict(refresh=RefreshConfig(mesh_shards=1),
                              prewarm=False),
    "fused_delta_sharded": dict(refresh=RefreshConfig(
        mesh_shards=MESH_SHARDS), prewarm=False),
    "fused_delta_skewed": dict(refresh=RefreshConfig(
        mesh_shards=MESH_SHARDS), prewarm=False),
    "fused_delta_balanced": dict(refresh=RefreshConfig(
        mesh_shards=MESH_SHARDS, lane_balance=0.25), prewarm=False),
}
DELTA_ARMS = ("fused_delta", "fused_delta_prewarm", "fused_delta_mesh1",
              "fused_delta_sharded", "fused_delta_skewed",
              "fused_delta_balanced")
# the straggler pair feeds every dirty slot to ONE shard (residue 0)
SKEWED_ARMS = ("fused_delta_skewed", "fused_delta_balanced")
# per-tick fraction of the queue whose PDGraph position changes between two
# delta ticks — ~5-10% is what open-arrival sims at 1 s buckets actually see
DIRTY_FRAC = 0.08
# the per-app looped baseline is O(queue) dispatches per tick; past 1k apps
# it would dominate the whole benchmark wall time for a known-linear curve.
# The full-walk arms are O(queue) walk lanes per tick: at the 16k+ sizes
# (which exist to scale the DELTA/mesh arms) they'd add minutes of wall per
# size for known-linear curves, so only fused_pallas follows as the
# full-walk reference
ARM_MAX_APPS = {
    "looped": 1024,
    "composed": 4096,
    "fused": 4096,
    "fused_prewarm": 4096,
    "fused_delta_prewarm": 16384,
    "fused_pallas": 16384,
    "fused_rank": 16384,
}


def build_queue(knowledge, n_apps: int, arm: str,
                seed: int = 11) -> HermesScheduler:
    sched = HermesScheduler(knowledge, policy="gittins", t_in=T_IN,
                            t_out=T_OUT, mc_walkers=MC_WALKERS, seed=seed,
                            **ARMS[arm])
    names = sorted(knowledge)
    rng = np.random.default_rng(seed)
    for i in range(n_apps):
        aid = f"app{i:05d}"
        sched.on_arrival(aid, names[i % len(names)],
                         now=float(rng.uniform(0.0, 100.0)))
        sched.on_progress(aid, float(rng.uniform(0.0, 5.0)))
    return sched


def make_dirty_marker(sched: HermesScheduler, knowledge, n_apps: int,
                      seed: int, skewed: bool = False):
    """Simulate the between-tick churn a live queue sees: a DIRTY_FRAC
    subset of applications takes a unit-(re)start event, which marks their
    slots dirty through the real scheduler event path.  ``skewed`` lands
    every dirty slot on shard 0 (residue placement): the worst-case
    dirty-imbalance the straggler arms measure."""
    n_dirty = max(int(DIRTY_FRAC * n_apps), 1)
    rng = np.random.default_rng(seed + 1)

    def mark():
        if skewed:
            pool = n_apps // MESH_SHARDS
            picks = rng.choice(pool, size=min(n_dirty, pool),
                               replace=False) * MESH_SHARDS
        else:
            picks = rng.choice(n_apps, size=n_dirty, replace=False)
        for i in picks:
            aid = f"app{i:05d}"
            app = sched.apps[aid]
            unit = app.current_unit or knowledge[app.app_name].entry
            sched.on_unit_start(aid, unit, 100.0)
    return mark


def time_refresh(sched: HermesScheduler, iters: int,
                 resample: bool, mark=None) -> Tuple[float, float]:
    """(mean, min) seconds per tick over `iters` timed ticks.  The min is
    the noise-robust estimator the CI trend gate compares (a single
    contended iteration must not read as a regression); the mean stays the
    headline number."""
    if mark is not None:
        mark()
    sched.refresh_tick(100.0, resample=resample)       # warmup / compile
    sched.take_prewarm_plan()
    if mark is not None:
        # a delta arm's FIRST tick walks the whole (all-dirty-on-admit)
        # queue; extra warmup ticks compile the delta-sized dispatches so
        # the timed ticks measure steady state, not jit tracing (the
        # per-shard max dirty count straddles two padded shapes at small
        # queues — several draws are needed to have seen both)
        for _ in range(4):
            mark()
            sched.refresh_tick(100.0, resample=resample)
            sched.take_prewarm_plan()
    sched.fused_spill = 0          # count spill over the timed ticks only
    times = []
    for _ in range(iters):
        if mark is not None:
            mark()                 # event cost stays outside the tick timing
        t0 = time.perf_counter()
        sched.refresh_tick(100.0, resample=resample)
        # consume the batched plan like a real host would: an untaken stash
        # would otherwise make later ticks pay a growing merge cost
        sched.take_prewarm_plan()
        times.append(time.perf_counter() - t0)
    return sum(times) / len(times), min(times)


def run(csv: Csv, paper_scale: bool = False, seed: int = 7,
        smoke: bool = False):
    if smoke:
        # 5 iters even in smoke: the trend gate compares min-of-N, and at
        # millisecond ticks the min needs several draws to converge
        sizes, iters = (16,), 5
    elif paper_scale:
        sizes, iters = (256, 1024, 4096, 8192, 16384, 32768), 3
    else:
        sizes, iters = (256, 1024, 4096, 16384), 3
    knowledge = kb()
    records = []
    per_size = {}
    mins = {}
    for n in sizes:
        ticks = {}
        for arm in ARMS:
            if n > ARM_MAX_APPS.get(arm, 1 << 30):
                continue
            if arm in ("fused_delta_sharded",) + SKEWED_ARMS \
                    and MESH_SHARDS < 2:
                continue   # no real mesh (jax imported first / 1 device):
                # the arms would duplicate fused_delta_mesh1 — skip them
            sched = build_queue(knowledge, n, arm, seed=seed)
            mark = (make_dirty_marker(sched, knowledge, n, seed,
                                      skewed=arm in SKEWED_ARMS)
                    if arm in DELTA_ARMS else None)
            # delta ticks are tens of ms with compile-adjacent variance:
            # the min-of-N estimator (what the trend gate and the sharded
            # acceptance ratio compare) needs more draws to converge than
            # the second-long full-walk ticks do
            n_iters = iters + 4 if arm in DELTA_ARMS else iters
            t, t_min = time_refresh(sched, n_iters, resample=True, mark=mark)
            ticks[arm] = t
            mins[(arm, n)] = t_min
            derived = f"{1e3 * t:.2f} ms/tick"
            if arm != "looped" and "looped" in ticks:
                derived += f" vs_looped={ticks['looped'] / t:.1f}x"
            if arm.startswith("fused") and "composed" in ticks:
                derived += f" vs_composed={ticks['composed'] / t:.2f}x"
            if arm in DELTA_ARMS and "fused_pallas" in ticks:
                derived += f" vs_full_fused={ticks['fused_pallas'] / t:.2f}x"
            if arm == "fused_pallas":
                derived += f" spill/tick={sched.fused_spill / iters:.0f}"
            if arm == "fused_rank" and ("fused_pallas", n) in mins:
                ratio = mins[("fused_pallas", n)] / t_min
                derived += f" vs_fused_pallas_min={ratio:.2f}x"
            if arm == "fused_delta_sharded":
                ratio = mins[("fused_delta", n)] / t_min
                derived += (f" shards={MESH_SHARDS}"
                            f" vs_1shard_min={ratio:.2f}x"
                            f" spill={sched.fused_spill}")
            if arm == "fused_delta_skewed" \
                    and ("fused_delta_sharded", n) in mins:
                gap = t_min - mins[("fused_delta_sharded", n)]
                derived += f" straggler_gap_min={1e3 * gap:.2f}ms"
            if arm == "fused_delta_balanced" \
                    and ("fused_delta_skewed", n) in mins:
                skew = mins[("fused_delta_skewed", n)]
                derived += f" vs_skewed_min={skew / t_min:.2f}x"
            csv.add(f"refresh_tick/full/{arm}/apps={n}", 1e6 * t, derived)
            row = {"name": f"refresh_tick/full/{arm}/apps={n}",
                   "arm": arm, "apps": n, "us_per_call": 1e6 * t,
                   "ms_per_tick": 1e3 * t, "ms_per_tick_min": 1e3 * t_min}
            if arm in DELTA_ARMS:
                row["dirty_frac"] = DIRTY_FRAC
            rc = ARMS[arm]["refresh"]
            if rc.mesh_shards is not None:
                row["mesh_shards"] = rc.mesh_shards
            if rc.lane_balance is not None:
                row["lane_balance"] = rc.lane_balance
            if arm in SKEWED_ARMS:
                row["skewed_dirty"] = True
            records.append(row)
        per_size[n] = ticks
    # rank-only tick (demand estimates cached between ticks)
    for n in sizes[-1:]:
        sched = build_queue(knowledge, n, "composed", seed=seed)
        t_rank, t_rank_min = time_refresh(sched, max(iters, 5),
                                          resample=False)
        csv.add(f"refresh_tick/rank_only/apps={n}", 1e6 * t_rank,
                f"{1e3 * t_rank:.3f} ms/tick")
        records.append({"name": f"refresh_tick/rank_only/apps={n}",
                        "arm": "rank_only", "apps": n,
                        "us_per_call": 1e6 * t_rank,
                        "ms_per_tick": 1e3 * t_rank,
                        "ms_per_tick_min": 1e3 * t_rank_min})
    speedups = {
        f"{arm}_vs_composed@{n}": ticks["composed"] / ticks[arm]
        for n, ticks in per_size.items() if "composed" in ticks
        for arm in ("fused", "fused_pallas", "fused_rank") if arm in ticks}
    # the ISSUE-9 acceptance ratio: one-pass fused_rank vs the legacy
    # composition, min-of-N estimator, per size
    speedups.update({
        f"fused_rank_vs_fused_pallas_min@{n}":
            mins[("fused_pallas", n)] / mins[("fused_rank", n)]
        for n, ticks in per_size.items()
        if ("fused_rank", n) in mins and ("fused_pallas", n) in mins})
    speedups.update({
        f"fused_delta_vs_full@{n}": ticks["fused_pallas"] / ticks["fused_delta"]
        for n, ticks in per_size.items()
        if "fused_delta" in ticks and "fused_pallas" in ticks})
    # the sharded acceptance ratio uses the min-of-N estimator (same one the
    # trend gate compares): mesh tick vs the 1-shard delta arm, per size
    speedups.update({
        f"fused_delta_sharded_vs_1shard_min@{n}":
            mins[("fused_delta", n)] / mins[("fused_delta_sharded", n)]
        for n, ticks in per_size.items() if "fused_delta_sharded" in ticks})
    speedups.update({
        f"fused_delta_sharded_vs_mesh1_min@{n}":
            mins[("fused_delta_mesh1", n)] / mins[("fused_delta_sharded", n)]
        for n, ticks in per_size.items()
        if "fused_delta_sharded" in ticks and "fused_delta_mesh1" in ticks})
    # dirty-imbalance straggler accounting (min-of-N): the gap is the cost
    # of the worst-case skewed dirty set over the uniform sharded tick; the
    # eliminated fraction is how much of that gap lane balancing buys back
    # (the ISSUE-9 balanced-mesh acceptance wants >= 0.5)
    straggler = {}
    for n, ticks in per_size.items():
        k_s, k_u, k_b = (("fused_delta_skewed", n),
                         ("fused_delta_sharded", n),
                         ("fused_delta_balanced", n))
        if k_s in mins and k_u in mins:
            gap = mins[k_s] - mins[k_u]
            straggler[f"gap_ms_min@{n}"] = 1e3 * gap
            if k_b in mins and gap > 0:
                straggler[f"eliminated_frac@{n}"] = \
                    (mins[k_s] - mins[k_b]) / gap
    payload = {
        "benchmark": "refresh_tick",
        "smoke": smoke,
        "mc_walkers": MC_WALKERS,
        "sizes": list(sizes),
        "iters": iters,
        "dirty_frac": DIRTY_FRAC,
        "mesh_shards": MESH_SHARDS,
        "devices": jax.device_count(),
        "platform": platform.platform(),
        "rows": records,
        "speedup": speedups,
        "straggler": straggler,
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
                    help="include the 8192-app point")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    csv = Csv()
    run(csv, paper_scale=args.paper, seed=args.seed, smoke=args.smoke)
    csv.dump()


if __name__ == "__main__":
    main()
