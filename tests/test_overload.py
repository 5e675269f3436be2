"""SLO-class admission/shedding, fairness, degradation, and overload
scenarios (PR 7).

Correctness contract:

* lifetime-stable accounting — admission's fairness ledger never goes
  negative, exits are idempotent, and a full churn drains to zero
  (hypothesis; deterministic stub in hermetic environments);
* every offered application reaches EXACTLY ONE terminal outcome
  (completed xor shed), no double-counted completions, arena slots are
  retired exactly once;
* the degradation latch engages above the high watermark, caps the MC
  walker depth, and restores full quality when pressure drains;
* (slow tier) hermes-with-shedding strictly dominates hermes-naive on
  goodput under a 10x flash crowd, without starving background tenants.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.apps.suite import T_IN, T_OUT, build_knowledge_base
from repro.apps.workload import (TenantProfile, assign_slo_mix,
                                 make_diurnal_workload,
                                 make_flash_crowd_workload,
                                 make_open_workload)
from repro.core.admission import (AdmissionConfig, AdmissionController,
                                  DegradeConfig, DegradeState,
                                  degrade_speedup)
from repro.core.refresh_config import RefreshConfig
from repro.serving.simulator import ClusterSim, SimConfig


@pytest.fixture(scope="module")
def kb():
    return build_knowledge_base(n_trials=120, seed=3)


def _run(kb, insts, **kw):
    base = dict(seed=5, prewarm_mode="lru", n_llm_slots=8, mc_walkers=64)
    base.update(kw)
    return ClusterSim(kb, SimConfig(**base)).run(list(insts))


# ----------------------------------------------------- accounting invariants

@given(st.lists(st.tuples(st.integers(0, 2), st.integers(0, 10 ** 6)),
                min_size=0, max_size=200))
@settings(max_examples=25, deadline=None)
def test_admission_ledger_lifetime_stable(ops):
    """Arbitrary admit/exit/double-exit churn: per-tenant live demand is
    never negative, equals the sum of its live apps' credited demand, and
    drains to exactly zero once every admitted app exits."""
    ctl = AdmissionController(AdmissionConfig())
    live = {}
    for i, (op, x) in enumerate(ops):
        app = f"a{x % 40}"
        tenant = f"t{x % 5}"
        if op == 0:
            if app not in live:          # admission is once per lifetime
                demand = 1.0 + (x % 7)
                ctl.note_admitted(app, tenant, demand)
                live[app] = (tenant, demand)
        elif op == 1:
            ctl.note_exit(app)
            live.pop(app, None)
        else:
            ctl.note_exit(app)           # double exit must be a no-op
            ctl.note_exit(app)
            live.pop(app, None)
        for t, acct in ctl.tenants.items():
            want = sum(d for tt, d in live.values() if tt == t)
            assert acct.live_demand >= 0.0
            assert abs(acct.live_demand - want) < 1e-6
    for app in list(live):
        ctl.note_exit(app)
    assert all(a.live_demand == 0.0 for a in ctl.tenants.values())


def test_fair_share_over_share():
    ctl = AdmissionController(AdmissionConfig(fair_share_slack=1.5))
    assert not ctl.over_share("t0")          # empty ledger: nobody is over
    ctl.note_admitted("a0", "t0", 10.0)
    ctl.note_admitted("a1", "t1", 10.0)
    assert not ctl.over_share("t0")
    # t0 now holds 40 of the 50 live: share 25, slack 1.5 -> cap 37.5
    ctl.note_admitted("a2", "t0", 30.0)
    assert ctl.over_share("t0")
    assert not ctl.over_share("t1")
    ctl.note_exit("a2")
    assert not ctl.over_share("t0")


def test_hopeless_decision_uses_optimistic_demand():
    ctl = AdmissionController()
    assert not ctl.hopeless(None, 0.0, 1e9)          # no deadline: never
    assert ctl.hopeless(10.0, 0.0, 11.0)
    assert not ctl.hopeless(10.0, 0.0, 9.0)
    assert ctl.hopeless(10.0, 0.0, 9.0, extra_wait=2.0)


# --------------------------------------------------- terminal-outcome rules

def _crowd(kb, **kw):
    base = dict(t_in=T_IN, t_out=T_OUT, base_load=0.8, spike_mult=8.0,
                spike_start=30.0, spike_dur=60.0, n_service_slots=8,
                with_deadlines=True, seed=2)
    base.update(kw)
    return make_flash_crowd_workload(240.0, **base)


def test_every_offered_app_has_exactly_one_terminal_outcome(kb):
    insts = _crowd(kb)
    res = _run(kb, insts, policy="hermes_ddl",
               admission=AdmissionConfig(pressure_watermark=1.0))
    offered = {i.app_id for i in insts}
    done = set(res.acts)
    shed = set(res.shed)
    assert done | shed == offered
    assert done & shed == set()                      # exactly one outcome
    assert sorted(res.completion_order) == sorted(done)
    assert len(set(res.completion_order)) == len(res.completion_order)
    # completed apps ran their whole trajectory exactly once
    by_id = {i.app_id: i for i in insts}
    for a in done:
        assert res.units_done[a] == len(by_id[a].trajectory)
    # shed apps are attributed a recorded reason
    assert all(r in ("hopeless_enqueue", "hopeless_midrun",
                     "pressure_reject", "defer_expired")
               for r in res.shed.values())
    # overload + deadlines: the sweep actually shed something here
    assert len(shed) > 0


def test_arena_slots_retired_exactly_once_under_shedding(kb):
    insts = _crowd(kb)
    sim = ClusterSim(kb, SimConfig(
        seed=5, prewarm_mode="lru", n_llm_slots=8, mc_walkers=64,
        policy="hermes_ddl", refresh=RefreshConfig(mode="fused_delta"),
        admission=AdmissionConfig(pressure_watermark=1.0)))
    res = sim.run(list(insts))
    qs = sim.sched._qstate
    assert qs is not None
    # every slot is either live or on a free-list, each exactly once
    frees = [i for f in qs._frees for i in f]
    assert len(frees) == len(set(frees))
    assert qs.live == len(qs.slot) == 0              # all work terminal
    assert len(frees) == len(qs._occ)
    assert not qs._occ.any()
    assert len(res.acts) + len(res.shed) == len(insts)


def test_shed_is_idempotent_on_scheduler(kb):
    insts = _crowd(kb)
    sim = ClusterSim(kb, SimConfig(
        seed=5, prewarm_mode="lru", n_llm_slots=8, mc_walkers=64,
        policy="hermes_ddl", refresh=RefreshConfig(mode="fused_delta"),
        admission=AdmissionConfig(pressure_watermark=1.0)))
    res = sim.run(list(insts))
    qs = sim.sched._qstate
    before = sum(len(f) for f in qs._frees)
    for app_id in list(res.shed) + list(res.acts):
        sim.sched.on_app_shed(app_id)                # second retire: no-op
    assert sum(len(f) for f in qs._frees) == before


def test_gold_never_shed_best_effort_first(kb):
    insts = assign_slo_mix(
        _crowd(kb, crowd_slo="best_effort"),
        {"gold": 0.2, "standard": 0.5, "best_effort": 0.3}, seed=9)
    # crowd instances keep best_effort: assign only overwrote uniformly,
    # so force gold on a known background subset instead
    for i in insts:
        if i.tenant == "crowd":
            i.slo = "best_effort"
    res = _run(kb, insts, policy="hermes_ddl",
               admission=AdmissionConfig(pressure_watermark=1.0))
    shed_slo = {res.slo[a] for a in res.shed}
    assert "gold" not in shed_slo
    assert len(res.shed) > 0


# ------------------------------------------------------------- degradation

def test_degrade_latch_hysteresis():
    d = DegradeState(DegradeConfig(high_watermark=3.0, low_watermark=1.0,
                                   llm_speedup=2.0))
    assert not d.update(2.0)           # below high: stays off
    assert d.update(3.5)               # crosses high: latches on
    assert d.update(2.0)               # between watermarks: stays on
    assert not d.update(0.5)           # below low: releases
    assert d.entered == 1
    assert not d.update(2.0)           # hysteresis: needs high again
    assert d.update(4.0)
    assert d.entered == 2


def test_degrade_speedup_from_zoo_is_clipped():
    s = degrade_speedup("llama3-8b", "qwen3-4b")
    assert 1.0 < s <= 4.0
    assert degrade_speedup("qwen3-4b", "llama3-8b") == 1.0   # never slows


def test_degradation_sheds_walker_depth_and_service(kb):
    insts = _crowd(kb, spike_mult=10.0)
    sim = ClusterSim(kb, SimConfig(
        seed=5, prewarm_mode="lru", n_llm_slots=8, mc_walkers=256,
        policy="gittins",
        admission=AdmissionConfig(pressure_watermark=1.0),
        degrade=DegradeConfig(high_watermark=1.5, low_watermark=0.5,
                              walker_cap=32, llm_speedup=2.0)))
    res = sim.run(list(insts))
    ds = res.degrade_stats
    assert ds["entered"] >= 1
    assert ds["degraded_units"] > 0
    assert ds["saved_service_s"] > 0.0
    assert ds["speedup"] == 2.0
    # full quality restored once the queue drained at the end of the run
    assert sim.sched.mc_walkers == 256
    assert len(res.acts) + len(res.shed) == len(insts)


# --------------------------------------------------------------- scenarios

def test_flash_crowd_workload_shape():
    insts = make_flash_crowd_workload(
        120.0, t_in=T_IN, t_out=T_OUT, base_load=0.8, spike_mult=10.0,
        spike_start=40.0, spike_dur=30.0, n_service_slots=16, seed=4)
    crowd = [i for i in insts if i.tenant == "crowd"]
    background = [i for i in insts if i.tenant != "crowd"]
    assert crowd and background
    assert all(40.0 <= i.arrival < 70.0 for i in crowd)
    assert all(i.slo == "best_effort" for i in crowd)
    assert all(i.deadline is not None for i in crowd)
    # ~9x the base rate landed inside the 30 s window
    base_rate = len(background) / 120.0
    crowd_rate = len(crowd) / 30.0
    assert crowd_rate > 3 * base_rate
    arr = [i.arrival for i in insts]
    assert arr == sorted(arr)


def test_diurnal_workload_shape():
    insts = make_diurnal_workload(200.0, t_in=T_IN, t_out=T_OUT,
                                  peak_load=2.0, trough_load=0.2,
                                  n_service_slots=32, seed=4)
    assert insts
    t = np.asarray([i.arrival for i in insts])
    # trough is at the window edges, peak mid-window
    mid = ((t > 50.0) & (t < 150.0)).sum()
    edge = len(t) - mid
    assert mid > edge
    assert all(i.app_id.startswith("diur") for i in insts)


def test_assign_slo_mix_covers_classes():
    insts = make_open_workload(600.0, t_in=T_IN, t_out=T_OUT,
                               target_load=2.0, n_service_slots=32, seed=1)
    assign_slo_mix(insts, {"gold": 1.0, "best_effort": 1.0}, seed=2)
    got = {i.slo for i in insts}
    assert got <= {"gold", "best_effort"}
    assert len(insts) > 10 and len(got) == 2


def test_tenant_profile_slo_flows_through():
    profiles = [TenantProfile(name="vip", slo="gold"),
                TenantProfile(name="bulk", slo="best_effort")]
    insts = make_open_workload(600.0, t_in=T_IN, t_out=T_OUT,
                               target_load=2.0, n_service_slots=32,
                               tenants=profiles, seed=1)
    assert {i.slo for i in insts if i.tenant == "vip"} <= {"gold"}
    assert {i.slo for i in insts if i.tenant == "bulk"} <= {"best_effort"}


# --------------------------------------------------------- goodput (slow)

@pytest.mark.slow
def test_shedding_dominates_naive_goodput_under_flash_crowd(kb):
    """The PR's headline claim: under a 10x flash crowd with deadlines,
    hermes-with-shedding beats hermes-naive on goodput (SLO-attaining
    completions per second), and the crowd tenant does not starve the
    background tenants."""
    insts = _crowd(kb, spike_mult=20.0, spike_dur=80.0, seed=6)
    naive = _run(kb, insts, policy="hermes_ddl")
    shed = _run(kb, insts, policy="hermes_ddl",
                admission=AdmissionConfig(pressure_watermark=1.0),
                degrade=DegradeConfig(high_watermark=2.0, low_watermark=0.5,
                                      llm_speedup=2.0))
    assert shed.goodput() > naive.goodput()
    # fairness: background (non-crowd) SLO attainment does not regress
    bg = [i.app_id for i in insts if i.tenant != "crowd"]

    def bg_attain(res):
        ok = sum(1 for a in bg if a in res.acts and res.dsr.get(a, True))
        return ok / len(bg)
    assert bg_attain(shed) >= bg_attain(naive)
