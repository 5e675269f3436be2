#!/usr/bin/env python3
"""On-chip smoke run of the Hermes refresh path.

One chip (the default): build the knowledge base and an overloaded
open-arrival trace from ``--seed`` (gamma arrivals, cv 2.5, 16 tenants, ten
times the load of 1024 service slots over 900 s), then drive ``ClusterSim``
with ``SimConfig(policy="gittins")`` — the default ``RefreshConfig``, so
every refresh tick runs the Pallas program on the device — until the slot
arena holds ``--backlog`` live applications and ``--ticks`` refresh ticks
have run at that backlog.  The last arena is then ranked twice on the chip,
by the kernel and by the oracle composition (jnp twin →
``to_histogram_rows_jnp`` → ``gittins_rank_core``) on the same rows and RNG
streams, and ``ranks``, ``probs`` and ``edges`` must agree bit for bit.

``--chips 4``: only the mesh-sharded arena
(``RefreshConfig(mesh_shards=4, lane_balance=0.25)``) against the
single-arena tick on the same arena and seeds: the shards must sit on four
distinct devices and the ranks must match bit for bit.

The script fails (non-zero exit, no result line) when JAX sees no TPU, when
any dispatch took the jnp twin or the Pallas interpreter, when the oracle
disagrees, or when any phase raises.  Its last line is
``{"ok": true, "device": {...}}``; compile and tick seconds it prints are
smoke readings, not benchmarks.

  python3 chip_smoke.py            # one chip
  python3 chip_smoke.py --chips 4  # four chips: sharded arena vs one arena
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import warnings
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

SIM_SCALE_TRACE = dict(duration_s=900.0, target_load=10.0,
                       n_service_slots=1024)
FALLBACK_WARNING = "pdgraph_walk: requested impl='pallas' fell back"
MARGIN = 2048              # applications in the trace beyond the backlog
MAX_EVENTS = 2_000_000     # hard cap on drained simulator events


def fail(msg: str) -> int:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    return 1


def compile_clock():
    """Seconds JAX has spent tracing, lowering and compiling so far."""
    import jax
    total = [0.0]

    def on_duration(event, secs, **_):
        if event.startswith("/jax/core/compile/"):
            total[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return lambda: total[0]


def ulp_diff(a, b):
    """Per-element distance in float32 units in the last place."""
    import numpy as np
    ia = np.asarray(a, np.float32).view(np.int32).astype(np.int64)
    ib = np.asarray(b, np.float32).view(np.int32).astype(np.int64)
    ia = np.where(ia < 0, -(ia & 0x7FFFFFFF), ia)
    ib = np.where(ib < 0, -(ib & 0x7FFFFFFF), ib)
    return np.abs(ia - ib)


def knowledge_base(seed):
    from repro.apps.suite import build_knowledge_base
    # 200 trials: the benchmarks' knowledge base (1,000 samples per unit)
    return build_knowledge_base(n_trials=200, seed=seed)


def trace(n_apps, seed):
    from repro.apps.suite import T_IN, T_OUT
    from repro.apps.workload import make_open_workload
    return make_open_workload(
        SIM_SCALE_TRACE["duration_s"], t_in=T_IN, t_out=T_OUT,
        target_load=SIM_SCALE_TRACE["target_load"],
        n_service_slots=SIM_SCALE_TRACE["n_service_slots"],
        process="gamma", cv=2.5, tenants=16, seed=seed, max_apps=n_apps)


def run_backlog(kb, insts, seed, backlog, ticks, max_events, clock):
    """Drive the simulator until ``ticks`` refresh ticks ran with at least
    ``backlog`` live applications.  Returns (sim, result, stats)."""
    from repro.serving.simulator import ClusterSim, SimConfig
    sim = ClusterSim(kb, SimConfig(policy="gittins", seed=seed))
    st = dict(peak_live=0, ticks=0, ticks_at_backlog=0, steady=[],
              policy_s=0.0, compile_s=clock(), last_tick=0.0)

    def progress(s):
        live = len(s.sched._live)
        st["peak_live"] = max(st["peak_live"], live)
        refresh_s = s.policy_time - st["policy_s"]
        st["policy_s"] = s.policy_time
        compiled = clock() - st["compile_s"]
        st["compile_s"] = clock()
        # a tick's micro-batch is the first one at its whole second (the
        # batch drains every event of that instant; events its handlers
        # push for the same instant come in later batches)
        if not float(s.now).is_integer() or s.now <= st["last_tick"]:
            return False
        st["last_tick"] = s.now
        st["ticks"] += 1
        if live < backlog:
            return False
        st["ticks_at_backlog"] += 1
        if compiled == 0.0:
            st["steady"].append(refresh_s)
        return st["ticks_at_backlog"] >= ticks

    res = sim.run(insts, max_events=max_events, progress=progress)
    return sim, res, st


def oracle_check(sched, interpret=False):
    """Rank the scheduler's arena on the chip by the kernel and by the
    oracle composition.  Returns (rows, {name: (mismatches, max ulp)},
    the kernel call's (LAST_DISPATCH, LAST_INTERPRET))."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.gittins import gittins_rank_core, to_histogram_rows_jnp
    from repro.kernels.pdgraph_walk import ops
    from repro.kernels.pdgraph_walk.ops import (pdgraph_walk,
                                                pdgraph_walk_ranked,
                                                walker_streams)
    qs = sched._qstate
    packed = sched._packed_kb()
    slots = qs.occupied()
    A = len(slots)
    gi, start, executed, attained, kid, rid, _, ovs, ovc = qs.gather(slots)
    with_ov = qs.override_apps > 0
    rows = dict(graph_idx=jnp.asarray(gi), start=jnp.asarray(start),
                executed=jnp.asarray(executed),
                streams=walker_streams(sched._seed, jnp.asarray(kid),
                                       jnp.asarray(rid)),
                ov_samples=jnp.asarray(ovs) if with_ov else None,
                ov_counts=jnp.asarray(ovc) if with_ov else None,
                valid=jnp.asarray(np.arange(len(gi)) < A))
    att = jnp.asarray(attained)
    W, nb = sched.mc_walkers, sched.n_buckets

    @jax.jit
    def kernel(rows, att):
        out = pdgraph_walk_ranked(
            packed.samples, packed.counts, packed.cum_trans,
            rows["graph_idx"], rows["start"], rows["executed"],
            rows["streams"], att, rows["ov_samples"], rows["ov_counts"],
            valid=rows["valid"], n_walkers=W, n_buckets=nb, impl="pallas",
            interpret=interpret)
        return out["ranks"], out["probs"], out["edges"]

    @jax.jit
    def oracle(rows, att):
        rem, _ = pdgraph_walk(
            packed.samples, packed.counts, packed.cum_trans,
            rows["graph_idx"], rows["start"], rows["executed"],
            rows["streams"], rows["ov_samples"], rows["ov_counts"],
            valid=rows["valid"], n_walkers=W, impl="ref",
            compact_schedule=())
        probs, edges = to_histogram_rows_jnp(
            att[:, None] + jnp.maximum(rem, 0.0), nb)
        return gittins_rank_core(probs, edges, att), probs, edges

    got = [np.asarray(x)[:A] for x in kernel(rows, att)]
    dispatch = (ops.LAST_DISPATCH, ops.LAST_INTERPRET)
    want = [np.asarray(x)[:A] for x in oracle(rows, att)]
    return A, {name: (int(np.sum(g != w)), int(ulp_diff(g, w).max()))
               for name, g, w in zip(("ranks", "probs", "edges"),
                                     got, want)}, dispatch


def one_chip(args, clock) -> int:
    from repro.kernels.pdgraph_walk import ops
    kb = knowledge_base(args.seed)
    insts = trace(args.backlog + MARGIN, args.seed)
    print(f"trace: {len(insts)} applications, last arrival at "
          f"{insts[-1].arrival:.1f} s simulated", flush=True)
    t0 = time.perf_counter()
    sim, res, st = run_backlog(kb, insts, args.seed, args.backlog,
                               args.ticks, MAX_EVENTS, clock)
    wall = time.perf_counter() - t0
    sched = sim.sched
    steady = st["steady"]
    print(f"peak live apps: {st['peak_live']}", flush=True)
    print(f"refresh ticks: {st['ticks']} ({st['ticks_at_backlog']} at "
          f">= {args.backlog} live apps)", flush=True)
    print(f"events: {sim.events_processed}, simulated {sim.now:.1f} s, "
          f"wall {wall:.1f} s", flush=True)
    print(f"ops.LAST_DISPATCH: {ops.LAST_DISPATCH!r} "
          f"(interpret={ops.LAST_INTERPRET})", flush=True)
    print(f"compile seconds (smoke reading): {clock():.1f}", flush=True)
    print("wall seconds per steady tick at backlog (smoke reading): "
          + (", ".join(f"{s:.3f}" for s in steady) if steady else "none"),
          flush=True)
    print(f"spill: {sched.fused_spill}", flush=True)
    print(f"apps completed: {len(res.acts)}", flush=True)
    if st["peak_live"] < args.backlog:
        return fail(f"backlog reached {st['peak_live']} < {args.backlog}")
    if st["ticks_at_backlog"] < args.ticks:
        return fail(f"only {st['ticks_at_backlog']} ticks at backlog")
    if ops.LAST_DISPATCH != "pallas" or ops.LAST_INTERPRET is not False:
        return fail("the refresh did not run the compiled kernel")

    A, cmp, dispatch = oracle_check(sched)
    print(f"oracle check over {A} arena rows: "
          + ", ".join(f"{k} mismatches={m} max_ulp={u}"
                      for k, (m, u) in cmp.items()), flush=True)
    if dispatch != ("pallas", False):
        return fail(f"the oracle check's kernel call ran {dispatch}")
    bad = [k for k, (m, _) in cmp.items() if m]
    if bad:
        return fail(f"kernel disagrees with the oracle on {bad}")
    return 0


def four_chips(args) -> int:
    import jax
    import numpy as np
    from repro.apps.suite import T_IN, T_OUT
    from repro.core.refresh_config import RefreshConfig
    from repro.core.scheduler import HermesScheduler
    kb = knowledge_base(args.seed)
    insts = trace(args.backlog, args.seed)
    scheds = {
        name: HermesScheduler(kb, policy="gittins", t_in=T_IN, t_out=T_OUT,
                              mc_walkers=256, seed=args.seed, refresh=rc)
        for name, rc in (("mesh", RefreshConfig(mesh_shards=4,
                                                lane_balance=0.25)),
                         ("single", RefreshConfig()))}

    def tick(name, now):
        s = scheds[name]
        t0 = time.perf_counter()
        ranks = s.priorities(now)
        jax.block_until_ready(s._qstate.d_probs)
        print(f"{name} tick at t={now}: {len(ranks)} ranks in "
              f"{time.perf_counter() - t0:.2f} s (smoke reading, compile "
              "included)", flush=True)
        return ranks

    ticks = {name: [] for name in scheds}
    for name, s in scheds.items():
        s.on_arrivals([(inst.app_id, inst.app_name, inst.tenant,
                        inst.deadline) for inst in insts], now=0.0)
        ticks[name].append(tick(name, 1.0))
    # skew the second tick's dirty set onto one shard (mesh slots = 0 mod
    # 4) so lane balancing has a straggler to move; both arenas get the
    # same events
    mesh_qs = scheds["mesh"]._qstate
    moved = [inst.app_id for inst in insts[::3]
             if mesh_qs.slot[inst.app_id] % 4 == 0]
    for s in scheds.values():
        for inst in insts[::3]:
            s.on_progress(inst.app_id, 0.05)
        for app_id in moved:
            s.on_unit_start(app_id, s.apps[app_id].current_unit, 1.5)
    print(f"tick 2 dirty rows per shard: "
          f"{[len(d) for d in mesh_qs._dirty]}", flush=True)
    for name in scheds:
        ticks[name].append(tick(name, 2.0))
    mesh = scheds["mesh"]
    devs = [d.id for d in mesh.refresh_mesh.mesh.devices.flat]
    placed = sorted(d.id for d in mesh._qstate.d_probs.sharding.device_set)
    print(f"mesh devices: {devs}; arena rows on devices {placed}", flush=True)
    if len(set(devs)) != 4 or len(placed) != 4:
        return fail("the four shards do not sit on four distinct devices")
    for k in range(2):
        m, s = ticks["mesh"][k], ticks["single"][k]
        ids = sorted(s)
        if sorted(m) != ids:
            return fail(f"tick {k + 1}: the arenas rank different apps")
        mv = np.asarray([m[i] for i in ids], np.float32)
        sv = np.asarray([s[i] for i in ids], np.float32)
        n_bad = int(np.sum(mv != sv))
        print(f"tick {k + 1}: {len(ids)} ranks, mismatches={n_bad} "
              f"max_ulp={int(ulp_diff(mv, sv).max())}", flush=True)
        if n_bad:
            return fail(f"tick {k + 1}: sharded ranks differ from one arena")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--backlog", type=int, default=16384,
                    help="live applications the arena must hold")
    ap.add_argument("--ticks", type=int, default=5,
                    help="refresh ticks to run at that backlog")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        return fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, str(SRC))

    import jax
    from repro.compile_cache import enable_compile_cache
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if device["platform"] != "tpu":
        return fail(f"JAX sees no TPU (platform {device['platform']!r}); "
                    "this smoke run has no CPU fallback")
    if device["count"] < args.chips:
        return fail(f"--chips {args.chips} but {device['count']} visible")
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = compile_clock()
    with warnings.catch_warnings():
        # a kernel dispatch that falls back to the twin is a failure
        warnings.filterwarnings("error", message=FALLBACK_WARNING,
                                category=RuntimeWarning)
        rc = four_chips(args) if args.chips == 4 else one_chip(args, clock)
    if rc:
        return rc
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
