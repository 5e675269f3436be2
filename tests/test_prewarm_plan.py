"""Batched device-resident prewarm planning + backend cold/warm accounting.

The fused refresh dispatch now returns per-(app, backend-class) prewarm
trigger quantiles computed from the SAME MC walk that feeds the Gittins
ranks.  These tests pin:

* rank-walk neutrality — arrival tracking must not change the demand samples;
* trigger semantics against the §3.4 closed form on deterministic graphs
  (quantile timing, K coverage gate, docker warm-up subtraction);
* the simulator's cold-start consequences — stall charged at a cold backend,
  no charge behind a correctly timed prewarm, wasted-warm seconds on a
  prewarm that never gets used.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.apps.workload import AppInstance
from repro.core.pdgraph import (ARRIVAL_NEVER, BackendSpec, PDGraph,
                                UnitNode, _mc_walk_batch, pack_graphs)
from repro.core.prewarm import PrewarmPlan
from repro.core.refresh_config import RefreshConfig
from repro.core.scheduler import HermesScheduler
from repro.serving.simulator import ClusterSim, SimConfig

T_IN, T_OUT = 1e-4, 2e-3
DOCKER_TP = 10.0          # warmup_time_for kind-fallback for unknown images


def _unit(name, image, durs, nxt):
    return UnitNode(name=name, backend=BackendSpec("docker", model=image),
                    duration=list(durs), next_counts=dict(nxt))


def _chain_kb(dur_a=30.0, dur_b=5.0):
    """Deterministic 2-unit docker chain: a (dur_a) -> b (dur_b) -> end."""
    units = {"a": _unit("a", "img-a", [dur_a] * 20, {"b": 20}),
             "b": _unit("b", "img-b", [dur_b] * 20, {"$end": 20})}
    return {"T": PDGraph("T", "a", units)}


def _branch_kb(p_b=0.5, dur_a=30.0):
    """a (dur_a) -> b with probability p_b, else end."""
    n_b = int(100 * p_b)
    units = {"a": _unit("a", "img-a", [dur_a] * 20,
                        {"b": n_b, "$end": 100 - n_b}),
             "b": _unit("b", "img-b", [5.0] * 20, {"$end": 20})}
    return {"T": PDGraph("T", "a", units)}


def _sched(kb, **kw):
    base = dict(policy="gittins", t_in=T_IN, t_out=T_OUT, mc_walkers=512,
                seed=3, prewarm=True,
                refresh=RefreshConfig(mode="fused_delta", walker="pallas"))
    base.update(kw)
    return HermesScheduler(kb, **base)


def _plan_of(kb, now=0.0, **kw) -> PrewarmPlan:
    s = _sched(kb, **kw)
    s.on_arrival("x", "T", now=now)
    s.priorities(now)
    plan = s.take_prewarm_plan()
    if plan is None:                       # nothing passed the coverage gate
        plan = PrewarmPlan([], [], [], np.zeros(0), np.zeros(0, np.float32))
    return plan


# ------------------------------------------------------------ walk neutrality
def test_arrival_tracking_keeps_rem_bit_identical():
    """Switching arrival tracking on must not perturb the demand samples —
    the prewarm planner rides the rank walk for free."""
    packed = pack_graphs(_chain_kb(), T_IN, T_OUT)
    gi = jnp.zeros(2, jnp.int32)
    st = jnp.asarray(packed.entry[np.zeros(2, np.int32)])
    ex = jnp.zeros(2, jnp.float32)
    ids = jnp.arange(2, dtype=jnp.int32)
    rid = jnp.zeros(2, jnp.int32)
    ovs = jnp.zeros((2, packed.n_units, 1), jnp.float32)
    ovc = jnp.zeros((2, packed.n_units), jnp.int32)
    key = jax.random.PRNGKey(0)
    plain = _mc_walk_batch(packed.samples, packed.counts, packed.cum_trans,
                           gi, st, ex, key, ids, rid, ovs, ovc, 64, 32)
    rem, arr = _mc_walk_batch(packed.samples, packed.counts,
                              packed.cum_trans, gi, st, ex, key, ids, rid,
                              ovs, ovc, 64, 32, track_arrivals=True)
    assert np.array_equal(np.asarray(plain), np.asarray(rem))
    assert arr.shape == (2, 64, packed.n_units)


# ------------------------------------------------------- trigger semantics
def test_deterministic_chain_trigger_timing():
    """§3.4 closed form on a deterministic chain: p_reach(b) = 1, arrival at
    b = dur_a, so the trigger fires at now + dur_a - t_p for ANY K."""
    for k_knob in (1.0, 0.5):
        plan = _plan_of(_chain_kb(dur_a=30.0), now=7.0, K=k_knob)
        by_key = {k: t for k, t in zip(plan.resource_keys, plan.fire_at)}
        assert "docker:img-b" in by_key
        assert by_key["docker:img-b"] == pytest.approx(7.0 + 30.0 - DOCKER_TP,
                                                       abs=0.5)
    # the entry unit is never "arrived at" by the walk — its backends are
    # the arrival-time (p_s = 1) prewarm, not part of the downstream plan
    assert "docker:img-a" not in by_key


def test_coverage_gate_matches_k_knob():
    """A p~0.5 branch prewarms only when K <= p_reach (Fig. 14 gate)."""
    kb = _branch_kb(p_b=0.5)
    keys_tight = _plan_of(kb, K=0.8).resource_keys
    assert "docker:img-b" not in keys_tight
    plan = _plan_of(kb, K=0.3)
    assert "docker:img-b" in plan.resource_keys
    i = plan.resource_keys.index("docker:img-b")
    assert plan.p_reach[i] == pytest.approx(0.5, abs=0.1)


def test_negative_trigger_clips_to_now():
    """Arrival sooner than the warm-up: fire immediately (partial overlap
    still shortens the stall) — same clip as the legacy planner."""
    plan = _plan_of(_chain_kb(dur_a=2.0), now=5.0)
    i = plan.resource_keys.index("docker:img-b")
    assert plan.fire_at[i] == pytest.approx(5.0)


def test_plan_covers_two_hops():
    """The batched plan generalizes the legacy one-hop planner: units two
    transitions downstream get triggers from the same dispatch."""
    units = {"a": _unit("a", "img-a", [10.0] * 20, {"b": 20}),
             "b": _unit("b", "img-b", [20.0] * 20, {"c": 20}),
             "c": _unit("c", "img-c", [5.0] * 20, {"$end": 20})}
    plan = _plan_of({"T": PDGraph("T", "a", units)})
    by_key = {k: t for k, t in zip(plan.resource_keys, plan.fire_at)}
    assert by_key["docker:img-b"] == pytest.approx(10.0 - DOCKER_TP,
                                                   abs=0.5)
    assert by_key["docker:img-c"] == pytest.approx(30.0 - DOCKER_TP,
                                                   abs=0.5)


def test_fused_prewarm_keeps_rank_parity():
    """Prewarm planning must not perturb the ranks of the same dispatch."""
    kb = _chain_kb()
    r_on = _sched(kb, prewarm=True)
    r_off = _sched(kb, prewarm=False)
    for s in (r_on, r_off):
        for i in range(6):
            s.on_arrival(f"p{i}", "T", now=0.5 * i)
    on = r_on.priorities(4.0)
    off = r_off.priorities(4.0)
    np.testing.assert_allclose([on[k] for k in sorted(on)],
                               [off[k] for k in sorted(off)],
                               rtol=1e-6)
    assert r_off.take_prewarm_plan() is None


def test_untaken_plans_dedup_instead_of_accumulating():
    """Ticks without a take_prewarm_plan consumer must not grow the stash
    unboundedly: merges dedup on (app, class), newest trigger wins."""
    s = _sched(_chain_kb())
    s.on_arrival("x", "T", now=0.0)
    for t in range(5):
        s.refresh_tick(float(t), resample=True)    # plan never taken
    plan = s.take_prewarm_plan()
    pairs = list(zip(plan.app_ids, plan.resource_keys))
    assert len(pairs) == len(set(pairs))
    assert len(plan) <= 2                          # img-b (+ loop revisits)


# ------------------------------------------------- simulator consequences
def _run_sim(kb, traj, prewarm_mode, **cfg_kw):
    cfg = SimConfig(policy="gittins", seed=5, prewarm_mode=prewarm_mode,
                    mc_walkers=64, **cfg_kw)
    inst = AppInstance(app_id="app000", app_name="T", tenant="t0",
                       arrival=0.0, trajectory=list(traj))
    return ClusterSim(kb, cfg).run([inst])


def test_cold_backend_charges_stall():
    """A unit arriving at a cold backend is charged the full warm-up on its
    critical path: ACT = warm-up + service, stall surfaced in the stats."""
    res = _run_sim(_chain_kb(), [("a", {"dur": 5.0})], "lru")
    assert res.prewarm_stats["coldstart_stall_s"] == pytest.approx(DOCKER_TP)
    assert res.prewarm_stats["coldstart_events"] == 1
    assert res.acts["app000"] == pytest.approx(DOCKER_TP + 5.0)


def test_timed_prewarm_removes_downstream_stall():
    """With the batched plan, img-b is warm before unit b arrives: only the
    entry backend stalls (its prewarm fires at arrival and overlaps the
    load), and the prewarmed entry counts as used, not wasted."""
    traj = [("a", {"dur": 30.0}), ("b", {"dur": 5.0})]
    cold = _run_sim(_chain_kb(), traj, "lru")
    warm = _run_sim(_chain_kb(), traj, "hermes")
    assert cold.prewarm_stats["coldstart_stall_s"] == \
        pytest.approx(2 * DOCKER_TP)
    # hermes: entry load overlaps nothing (task starts instantly) but unit b
    # was prewarmed at ~20s, warm at ~30s, needed at ~40s -> zero charge
    assert warm.prewarm_stats["coldstart_stall_s"] == pytest.approx(DOCKER_TP)
    assert warm.acts["app000"] == cold.acts["app000"] - DOCKER_TP
    assert warm.prewarm_stats["spec_used"] >= 2      # img-a@arrival + img-b
    assert warm.prewarm_stats["wasted_warm_s"] == pytest.approx(0.0)


def test_unused_prewarm_counts_wasted_warm():
    """A prewarm for a branch the app never takes stays resident unused —
    its warm seconds are charged to wasted_warm_s, not silently dropped."""
    res = _run_sim(_branch_kb(p_b=0.5), [("a", {"dur": 30.0})], "hermes",
                   K=0.3)
    p = res.prewarm_stats
    assert p["spec_loads"] > p["spec_used"]
    assert p["wasted_warm_s"] > 0.0


def test_keep_alive_knob_controls_speculative_eviction():
    """keep_alive_s is the idle threshold below which speculative loads may
    not evict warm entries (thrash guard)."""
    from repro.core.hermeslet import HermesLet
    let = HermesLet(dnn_capacity=1, keep_alive_s=100.0)
    assert let.prewarm("dnn:m1", 0.0) is not None
    let.access("dnn:m1", 50.0)                      # hot at t=50
    assert let.prewarm("dnn:m2", 60.0) is None      # idle 10 < 100: refused
    let2 = HermesLet(dnn_capacity=1, keep_alive_s=5.0)
    assert let2.prewarm("dnn:m1", 0.0) is not None
    let2.access("dnn:m1", 50.0)
    assert let2.prewarm("dnn:m2", 60.0) is not None  # idle 10 >= 5: evicted


# ------------------------------------------------------------- engine glue
def test_engine_applies_llm_side_of_plan():
    from repro.serving.engine import InferenceEngine
    eng = InferenceEngine.__new__(InferenceEngine)
    eng.prefix_prompts = {"P1": [1, 2, 3]}
    eng.lora = type("L", (), {"adapters": {"l0": object()}})()
    calls = []
    eng.prewarm_prefix = lambda p: calls.append(("kv", p))
    eng.prewarm_lora = lambda n: calls.append(("lora", n))
    plan = PrewarmPlan(app_ids=["a", "a", "a", "a"],
                       resource_keys=["kv:P1", "kv:P9", "lora:l0",
                                      "docker:img"],
                       kinds=["llm", "llm", "llm", "docker"],
                       fire_at=np.asarray([0.0, 0.0, 50.0, 0.0]),
                       p_reach=np.ones(4, np.float32))
    acted = eng.apply_prewarm_plan(plan, now=10.0)
    assert acted == 1                      # lora not due yet; P9/docker skip
    assert calls == [("kv", "P1")]
    assert eng.apply_prewarm_plan(plan, now=60.0) == 2   # lora now due
    assert ("lora", "l0") in calls
    assert eng.apply_prewarm_plan(plan) == 2             # None = apply all
    assert eng.apply_prewarm_plan(None) == 0


def test_model_zoo_warmup_table_scales_with_architecture():
    from repro.core.hermeslet import (DEFAULT_WARMUP_S,
                                      warmup_table_from_model)
    ref = warmup_table_from_model("llama3-8b")
    assert ref["kv"] == pytest.approx(DEFAULT_WARMUP_S["kv"])
    assert ref["lora"] == pytest.approx(DEFAULT_WARMUP_S["lora"])
    small = warmup_table_from_model("qwen3-4b")
    assert small["lora"] < ref["lora"]     # fewer params -> faster load


def test_arrival_never_sentinel_is_plan_threshold():
    """from_triggers drops exactly the ARRIVAL_NEVER-marked cells."""
    from repro.core.prewarm import PrewarmPlan, PrewarmTable
    tab = PrewarmTable(classes=("docker:x", "kv:y"), kinds=("docker", "llm"),
                       unit_class=np.zeros((1, 1, 1), np.int32),
                       warmup=np.zeros(2, np.float32))
    trig = np.asarray([[5.0, ARRIVAL_NEVER], [-3.0, 2.0]], np.float32)
    reach = np.full((2, 2), 0.9, np.float32)
    plan = PrewarmPlan.from_triggers(["a0", "a1"], trig, reach,
                                     now=100.0, table=tab)
    got = {(a, k): t for a, k, t in
           zip(plan.app_ids, plan.resource_keys, plan.fire_at)}
    assert got == {("a0", "docker:x"): 105.0, ("a1", "docker:x"): 100.0,
                   ("a1", "kv:y"): 102.0}
