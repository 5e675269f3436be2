"""A refresh dispatch is named by the simulator's call that made it.

``ClusterSim._refresh_ranks`` is called without ids by a bucket tick and
with the batch's ids by an event batch; it passes that as ``caller`` to
the scheduler, whose single-arena delta dispatch writes its spans
(``hermes.<caller>.{prepare,wait,consume}``) and counts itself
(``<caller>_dispatches``) under that name.  What the dispatch does still
follows whether it re-ranks everything.  Pinned here:

* under ``hermes_ddl``, whose every event batch re-ranks the whole arena,
  the event batches' dispatches are ``event`` dispatches: the tick count
  is the bucket ticks', and each dispatch writes one ``hermes.key`` span;
* under ``gittins`` the naming is the one ``retrigger`` gave, and
  ``hermes.key`` is the only new span;
* ranks, triage mirrors, prewarm plans and the run's outcome are the same
  bits as with the scheduler called without ``caller`` (the naming by
  ``retrigger``), for both policies.
"""
import collections
import glob

import numpy as np
import pytest

import jax

from repro.apps.suite import T_IN, T_OUT, build_knowledge_base
from repro.apps.workload import make_open_workload
from repro.core import scheduler as scheduler_mod
from repro.core.refresh_config import RefreshConfig
from repro.core.scheduler import HermesScheduler
from repro.runtime.tracing import PREFIX
from repro.serving.simulator import ClusterSim, SimConfig

REFRESH = RefreshConfig(mode="fused_delta", walker="pallas")
PHASES = ("prepare", "wait", "consume")


@pytest.fixture(scope="module")
def kb():
    return build_knowledge_base(n_trials=40, seed=0)


def _sim(kb, policy):
    insts = make_open_workload(30.0, t_in=T_IN, t_out=T_OUT, rate_per_s=0.5,
                               process="gamma", cv=2.5, seed=5, max_apps=12,
                               with_deadlines=policy == "hermes_ddl")
    sim = ClusterSim(kb, SimConfig(policy=policy, mc_walkers=32, seed=3,
                                   n_llm_slots=4, n_docker_slots=6,
                                   n_dnn_slots=2, refresh=REFRESH))
    return sim, insts


def _follow(sim, monkeypatch):
    """Record, for every single-arena dispatch, the simulator's caller and
    the dispatch's ``retrigger``, ranks and the arena's triage mirrors
    after it; every prewarm plan stashed; the rank column after every
    refresh call."""
    log = {"dispatches": [], "plans": [], "rank_arr": []}
    caller = [None]
    orig_refresh = sim._refresh_ranks

    def refresh_ranks(app_ids=None, touched=None):
        caller[0] = "tick" if app_ids is None else "event"
        out = orig_refresh(app_ids, touched=touched)
        caller[0] = None
        log["rank_arr"].append(sim._rank_arr.copy())
        return out
    sim._refresh_ranks = refresh_ranks

    orig_delta = scheduler_mod.refresh_ranks_delta

    def refresh_ranks_delta(packed, qs, *a, **kw):
        tick = orig_delta(packed, qs, *a, **kw)
        log["dispatches"].append(
            (caller[0], kw["retrigger"], np.asarray(tick.ranks).copy(),
             qs.sup.copy(), qs.opt.copy(), qs.mean.copy()))
        return tick
    monkeypatch.setattr(scheduler_mod, "refresh_ranks_delta",
                        refresh_ranks_delta)

    orig_stash = HermesScheduler._stash_plan

    def stash(self, plan):
        log["plans"].append((list(plan.app_ids), list(plan.resource_keys),
                             list(plan.kinds), plan.fire_at.copy(),
                             plan.p_reach.copy()))
        return orig_stash(self, plan)
    monkeypatch.setattr(HermesScheduler, "_stash_plan", stash)
    return log


def _profiled_run(sim, insts, tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        sim.run(insts)
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(sorted(files)[-1])
    return collections.Counter(
        e.name for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith(PREFIX))


@pytest.mark.parametrize("policy", ["gittins", "hermes_ddl"])
def test_dispatches_are_named_by_caller(kb, tmp_path, monkeypatch, policy):
    sim, insts = _sim(kb, policy)
    log = _follow(sim, monkeypatch)
    n = _profiled_run(sim, insts, tmp_path)
    stats = sim.sched.refresh_stats
    callers = collections.Counter(d[0] for d in log["dispatches"])
    assert None not in callers
    assert stats["tick_dispatches"] == callers["tick"] > 0
    assert stats["event_dispatches"] == callers["event"] > 0
    for path in ("event", "tick"):
        assert n[f"hermes.{path}.wait"] == stats[f"{path}_dispatches"]
        # the scheduler's and the pipeline's part of each phase
        assert n[f"hermes.{path}.prepare"] == 2 * n[f"hermes.{path}.wait"]
        assert n[f"hermes.{path}.consume"] == 2 * n[f"hermes.{path}.wait"]
    assert n["hermes.key"] == len(log["dispatches"])
    assert set(n) == {f"hermes.{p}.{ph}" for p in ("event", "tick")
                      for ph in PHASES} | {"hermes.rekey", "hermes.key"}
    full_events = sum(c == "event" and rt for c, rt, *_ in log["dispatches"])
    if policy == "gittins":
        # a bucket tick re-ranks everything, an event batch its own ids:
        # the caller's name is the one retrigger gave
        assert all((c == "tick") == rt for c, rt, *_ in log["dispatches"])
    else:
        # every dispatch re-ranks everything; the event batches' are
        # event dispatches all the same
        assert all(rt for _, rt, *_ in log["dispatches"])
        assert full_events == stats["event_dispatches"]


@pytest.mark.parametrize("policy", ["gittins", "hermes_ddl"])
def test_naming_changes_no_bit(kb, monkeypatch, policy):
    def run(named):
        with monkeypatch.context() as m:
            sim, insts = _sim(kb, policy)
            log = _follow(sim, m)
            if not named:
                # the scheduler called without caller: named by retrigger
                orig = HermesScheduler.priorities_arrays
                m.setattr(HermesScheduler, "priorities_arrays",
                          lambda self, now, app_ids=None, caller=None:
                          orig(self, now, app_ids))
            res = sim.run(insts)
            return sim, res, log

    sim_a, res_a, a = run(True)
    sim_b, res_b, b = run(False)
    assert len(a["dispatches"]) == len(b["dispatches"]) > 0
    for da, db in zip(a["dispatches"], b["dispatches"]):
        assert da[0] == db[0] and da[1] == db[1]
        for xa, xb in zip(da[2:], db[2:]):
            assert xa.tobytes() == xb.tobytes()
    assert len(a["plans"]) == len(b["plans"]) > 0
    for pa, pb in zip(a["plans"], b["plans"]):
        assert pa[:3] == pb[:3]
        assert pa[3].tobytes() == pb[3].tobytes()
        assert pa[4].tobytes() == pb[4].tobytes()
    assert len(a["rank_arr"]) == len(b["rank_arr"])
    assert all(x.tobytes() == y.tobytes()
               for x, y in zip(a["rank_arr"], b["rank_arr"]))
    assert res_a.completion_order == res_b.completion_order
    assert res_a.acts == res_b.acts and res_a.dsr == res_b.dsr
    sa, sb = sim_a.sched.refresh_stats, sim_b.sched.refresh_stats
    # uploads differ by the resident constants, which only the first run
    # in the process sends; reads are per dispatch, each one's copy
    # started at the enqueue
    assert sa["d2h"] == sb["d2h"]
    assert sa["d2h_async"] == sb["d2h_async"] == sa["d2h"]
    total = len(a["dispatches"])
    assert sa["event_dispatches"] + sa["tick_dispatches"] == total
    assert sb["event_dispatches"] + sb["tick_dispatches"] == total
    ticks = sum(d[0] == "tick" for d in a["dispatches"])
    assert sa["tick_dispatches"] == ticks
    retriggered = sum(d[1] for d in a["dispatches"])
    assert sb["tick_dispatches"] == retriggered
    if policy == "gittins":
        assert sa["tick_dispatches"] == sb["tick_dispatches"]
    else:
        # named by retrigger, every event batch's re-rank was a tick
        assert sb["event_dispatches"] == 0 < sa["event_dispatches"]
