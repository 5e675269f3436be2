"""Persistent slot store lifecycle + dirty-set delta refresh + fused triage.

Pins the PR's three contracts:

* the slot-store lifecycle (admit/retire/grow) keeps slot ids stable for an
  app's whole lifetime, reuses freed slots, and partitions the arena into
  occupied ∪ free under an arbitrary churn sequence (hypothesis);
* a delta tick is **bit-identical** to a full re-walk of the same dirty set
  (the acceptance claim behind the fused_delta benchmark arm), and the
  dirty-set semantics walk exactly what changed;
* the composite policies' on-device triage (`hermes_ddl`/`lstf` in
  ``mode="fused_delta"``) matches the host-quantile path on float32 with
  no sample arrays ever reaching the host.
"""
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.apps.suite import T_IN, T_OUT, build_knowledge_base
from repro.core.pdgraph import (ARRIVAL_NEVER, BackendSpec, PDGraph,
                                UnitNode, pack_graphs)
from repro.core.prewarm import prewarm_trigger_time
from repro.core.arena import QueueState
from repro.core.refresh_pipeline import refresh_ranks_delta
from repro.core.refresh_config import RefreshConfig
from repro.core.scheduler import HermesScheduler

MC = 32


@pytest.fixture(scope="module")
def kb():
    return build_knowledge_base(n_trials=60, seed=3)


@pytest.fixture(scope="module")
def packed(kb):
    return pack_graphs(kb, T_IN, T_OUT)


def _filled(kb, mode, walker="threefry", n_apps=24, policy="gittins",
            refresh_kw=None, **kw):
    rc = RefreshConfig(mode=mode, walker=walker, **(refresh_kw or {}))
    s = HermesScheduler(kb, policy=policy, t_in=T_IN, t_out=T_OUT,
                        mc_walkers=MC, seed=11, refresh=rc, **kw)
    names = sorted(kb)
    for i in range(n_apps):
        aid = f"a{i:03d}"
        s.on_arrival(aid, names[i % len(names)], now=0.25 * i,
                     tenant=f"t{i % 4}", deadline=200.0 + 3.0 * i)
        s.on_progress(aid, 0.05 * i)
    return s


def _vals(ranks):
    ids = sorted(ranks)
    return ids, np.asarray([ranks[i] for i in ids])


# ------------------------------------------------------------ churn lifecycle
_TINY = None


def _tiny_packed():
    """Module-lazy packed KB for the hypothesis churn test (fixtures can't
    mix with @given under the hermetic stub)."""
    global _TINY
    if _TINY is None:
        _TINY = pack_graphs(_chain_kb(), T_IN, T_OUT)
    return _TINY


@given(st.lists(st.tuples(st.integers(0, 2), st.integers(0, 10 ** 6)),
                min_size=1, max_size=120))
@settings(max_examples=25, deadline=None)
def test_slot_store_churn_invariants(ops):
    """Arbitrary admit/retire/progress churn: slots stay pinned for an
    app's lifetime, freed slots are reused (not leaked), occupied and free
    partition a power-of-two arena, and host rows survive in place."""
    packed = _tiny_packed()
    qs = QueueState(packed, capacity=4)
    mirror = {}
    seq = 0
    for kind, r in ops:
        if kind == 0 or not mirror:                       # admit
            aid = f"app{seq}"
            start = r % packed.n_units
            slot = qs.admit(aid, 0, start, key_id=seq)
            mirror[aid] = [slot, start, seq, 0.0]
            seq += 1
            assert qs.ids[slot] == aid and slot in qs.dirty
        elif kind == 1:                                   # retire
            aid = sorted(mirror)[r % len(mirror)]
            slot = mirror.pop(aid)[0]
            qs.retire(aid)
            assert qs.ids[slot] is None
            assert not qs._occ[slot] and slot not in qs.dirty
        else:                                             # progress
            aid = sorted(mirror)[r % len(mirror)]
            qs.add_progress(aid, 0.5)
            mirror[aid][3] += 0.5
    assert len(qs) == len(mirror) and sorted(qs.slot) == sorted(mirror)
    cap = qs.capacity
    assert cap & (cap - 1) == 0                           # pow2, grown 2x
    occ, free = set(qs.occupied().tolist()), set(qs._free)
    assert occ | free == set(range(cap)) and not (occ & free)
    for aid, (slot, start, key, att) in mirror.items():
        assert qs.slot[aid] == slot                       # never relocated
        assert qs.start[slot] == start and qs.key_id[slot] == key
        assert qs.attained[slot] == pytest.approx(att)
    # every freed slot is reachable again: admits fill holes before growing
    grown = cap
    for i in range(len(free)):
        qs.admit(f"fill{i}", 0, 0, key_id=1000 + i)
    assert qs.capacity == grown


def test_retired_slot_is_reused_before_growth(packed):
    qs = QueueState(packed, capacity=2)
    a = qs.admit("a", 0, 0, key_id=0)
    qs.admit("b", 0, 0, key_id=1)
    qs.retire("a")
    c = qs.admit("c", 0, 0, key_id=2)
    assert c == a and qs.capacity == 2                    # hole reused
    qs.admit("d", 0, 0, key_id=3)
    assert qs.capacity == 4                               # then doubled


# --------------------------------------------------- delta-tick bit identity
def test_delta_bit_identical_to_full_rewalk_of_dirty_set(kb):
    """Acceptance: delta-refreshed ranks for the dirty apps equal a full
    re-walk of the whole arena at the same slots to the BIT — gather → walk
    → scatter → rank-in-place must not perturb a single float."""
    for walker in ("threefry", "pallas"):
        subset, whole = (_filled(kb, "fused_delta", walker=walker)
                         for _ in range(2))
        ticks = []
        for s in (subset, whole):
            s.priorities(10.0)              # prime: all slots walked once
            qs = s._qstate
            dirty = qs.occupied()[::3]      # any subset
            walked = dirty if s is subset else qs.occupied()
            ticks.append(refresh_ranks_delta(
                s._packed[1], qs, s._base_key, s._seed, walked=walked,
                n_walkers=MC, walker=walker))
        np.testing.assert_array_equal(ticks[0].ranks[dirty],
                                      ticks[1].ranks[dirty], err_msg=walker)


@pytest.mark.parametrize("policy", ["gittins", "hermes_ddl", "lstf"])
def test_delta_subset_tick_matches_first_full_tick(kb, policy):
    """Admitting a queue in two parts (the second tick walks the 8 new
    slots as a dirty subset) ranks exactly like admitting it at once (the
    first tick walks everything): same streams, same math, bitwise — for
    the Gittins rank and for the composite policies' device triage."""
    rd = _filled(kb, "fused_delta", policy=policy).priorities(10.0)
    s = _filled(kb, "fused_delta", policy=policy, n_apps=16)
    s.priorities(10.0)
    names = sorted(kb)
    for i in range(16, 24):
        aid = f"a{i:03d}"
        s.on_arrival(aid, names[i % len(names)], now=0.25 * i,
                     tenant=f"t{i % 4}", deadline=200.0 + 3.0 * i)
        s.on_progress(aid, 0.05 * i)
    rs = s.priorities(10.0)
    assert sum(a.refreshes for a in s.apps.values()) == 24   # no re-walk
    ids_d, vd = _vals(rd)
    ids_s, vs = _vals(rs)
    assert ids_d == ids_s
    np.testing.assert_array_equal(vd, vs)


# ------------------------------------------------------- dirty-set semantics
def test_progress_only_tick_reranks_without_rewalk(kb):
    """Progress doesn't dirty a slot: the next tick re-ranks in place from
    the persisted device histograms (no MC walk), yet the rank moves with
    the new attained service."""
    s = _filled(kb, "fused_delta")
    r1 = s.refresh_tick(10.0, resample=True)
    before = {a.app_id: a.refreshes for a in s.apps.values()}
    s.on_progress("a000", 2.0)
    r2 = s.refresh_tick(11.0, resample=True)
    assert all(a.refreshes == before[a.app_id] for a in s.apps.values())
    assert r2["a000"] != r1["a000"]


def test_transition_walks_exactly_the_dirty_app(kb):
    s = _filled(kb, "fused_delta")
    s.refresh_tick(10.0, resample=True)
    before = {a.app_id: a.refreshes for a in s.apps.values()}
    s.on_unit_start("a002", s.apps["a002"].current_unit, 11.0)
    s.refresh_tick(11.0, resample=True)
    walked = [a.app_id for a in s.apps.values()
              if a.refreshes != before[a.app_id]]
    assert walked == ["a002"]


def test_dirty_fraction_fallback_walks_everything(kb):
    """Past delta_full_threshold the tick re-walks the whole occupied set
    (subset gather/scatter no longer pays)."""
    s = _filled(kb, "fused_delta", n_apps=12,
                refresh_kw={"delta_full_threshold": 0.25})
    s.refresh_tick(10.0, resample=True)
    before = {a.app_id: a.refreshes for a in s.apps.values()}
    for aid in ("a001", "a004", "a007"):    # 3/12 = 25% >= threshold
        s.on_unit_start(aid, s.apps[aid].current_unit, 11.0)
    s.refresh_tick(11.0, resample=True)
    assert all(a.refreshes == before[a.app_id] + 1
               for a in s.apps.values() if not a.done)


def test_delta_survives_retirement_churn(kb):
    """Retire a few apps (holes in the arena), admit a new one into a hole,
    keep ticking: ranks stay attached to the right apps and the new app is
    walked before its first rank is consumed."""
    s = _filled(kb, "fused_delta", n_apps=12)
    s.priorities(10.0)
    s.on_app_complete("a001")
    s.on_app_complete("a004")
    s.on_arrival("fresh", sorted(s.kb)[0], now=11.0)
    r = s.priorities(11.0)
    assert "a001" not in r and "a004" not in r and "fresh" in r
    assert s.apps["fresh"].refreshes == 1          # walked on admission tick
    assert np.isfinite(list(r.values())).all()


# --------------------------------------------------------- device triage
@pytest.mark.parametrize("policy", ["hermes_ddl", "lstf"])
def test_composite_policy_fused_matches_host_path(kb, policy):
    """hermes_ddl / lstf with mode='fused_delta': triage quantiles come
    from the device dispatch (no sample arrays on host) and the ranks match
    the composed host-quantile path to float32 tolerance."""
    r_host = _filled(kb, "composed", policy=policy).priorities(10.0)
    s = _filled(kb, "fused_delta", walker="threefry", policy=policy)
    assert s._delta_active()
    r_fused = s.priorities(10.0)
    ids_h, vh = _vals(r_host)
    ids_f, vf = _vals(r_fused)
    assert ids_h == ids_f
    np.testing.assert_allclose(vh, vf, rtol=1e-5, atol=1e-3)
    assert np.array_equal(np.argsort(vh, kind="stable"),
                          np.argsort(vf, kind="stable"))
    qs = s._qstate
    occ = qs.occupied()
    for a in s.apps.values():       # no per-app host quantile pulls possible
        assert a.view is None
    for col in (qs.sup, qs.opt, qs.mean):   # the device triage's mirrors
        assert np.isfinite(col[occ]).all()


def test_retuned_quantiles_fall_back_to_host_path(kb):
    """A policy instance re-tuned away from the device quantiles loses
    device eligibility instead of silently ranking on the wrong quantile."""
    s = _filled(kb, "fused_delta", policy="lstf")
    s.policy.sup_q = 0.95
    assert not s._delta_active()
    r = s.priorities(10.0)                  # composed fallback still ranks
    assert len(r) == 24
    assert any(a.view.total_samples is not None for a in s.apps.values())


def test_retune_mid_run_reestimates_stale_fused_views(kb):
    """Re-tuning AFTER device ticks must estimate every app on the host
    path (the device path minted no views, and its scalars are pinned to
    the stock quantiles) — including a post-retune arrival."""
    s = _filled(kb, "fused_delta", policy="lstf")
    s.priorities(10.0)                      # device tick: no views minted
    s.policy.sup_q = 0.95
    s.on_arrival("late", sorted(s.kb)[0], now=11.0, deadline=300.0)
    r = s.priorities(11.0)                  # mixed views must not crash
    assert len(r) == 25 and np.isfinite(list(r.values())).all()
    assert all(a.view.total_samples is not None
               for a in s.apps.values() if not a.done)


# ------------------------------------------------------------------- repack
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 10 ** 6)),
                min_size=8, max_size=150))
@settings(max_examples=25, deadline=None)
def test_repack_churn_invariants(ops):
    """grow -> shrink -> grow churn with interleaved explicit repacks: the
    arena stays a valid pow-2 partition, every live app keeps its row
    values across renumbering, and slot ids change ONLY at repack epochs."""
    packed = _tiny_packed()
    qs = QueueState(packed, capacity=4)
    mirror = {}
    seq = 0
    for kind, r in ops:
        if kind == 0 or (kind != 3 and not mirror):       # admit
            aid = f"app{seq}"
            qs.admit(aid, 0, r % packed.n_units, key_id=seq)
            mirror[aid] = [seq, 0.0]
            seq += 1
        elif kind == 1:                                   # retire
            aid = sorted(mirror)[r % len(mirror)]
            mirror.pop(aid)
            qs.retire(aid)
        elif kind == 2:                                   # progress
            aid = sorted(mirror)[r % len(mirror)]
            qs.add_progress(aid, 0.5)
            mirror[aid][1] += 0.5
        else:                                             # repack epoch
            epoch = qs.repack_epoch
            snapshot = {a: qs.slot[a] for a in mirror}
            mapping = qs.repack()
            assert qs.repack_epoch == epoch + 1
            assert sorted(mapping) == sorted(snapshot.values())
            for aid, old in snapshot.items():
                assert qs.slot[aid] == mapping[old]       # remapped, once
    cap = qs.capacity
    assert cap & (cap - 1) == 0
    occ, free = set(qs.occupied().tolist()), set(qs._free)
    assert occ | free == set(range(cap)) and not (occ & free)
    assert len(qs) == len(mirror) and sorted(qs.slot) == sorted(mirror)
    for aid, (key, att) in mirror.items():
        s = qs.slot[aid]
        assert qs.ids[s] == aid and qs.key_id[s] == key
        assert qs.attained[s] == pytest.approx(att)
    # dirty/rank-dirty marks must reference live slots only
    assert qs.dirty <= occ and qs.rank_dirty <= occ


def test_scheduler_repacks_at_tick_boundary(kb):
    """Legacy delta path: a mostly-retired queue shrinks its arena on the
    next full tick, preserving every survivor's rank WITHOUT a re-walk
    (persisted device histogram rows are remapped, not rebuilt)."""
    s = _filled(kb, "fused_delta", n_apps=96)
    r1 = s.refresh_tick(10.0, resample=True)
    qs = s._qstate
    cap0 = qs.capacity
    for i in range(88):
        s.on_app_complete(f"a{i:03d}")
    before = {a.app_id: a.refreshes for a in s.apps.values() if not a.done}
    r2 = s.refresh_tick(11.0, resample=True)
    assert qs.capacity < cap0 and qs.repack_epoch == 1
    for aid, n in before.items():
        assert r2[aid] == r1[aid]
        assert s.apps[aid].refreshes == n


def test_small_arena_never_repacks(kb):
    s = _filled(kb, "fused_delta", n_apps=4)
    s.refresh_tick(10.0, resample=True)
    assert s._qstate.capacity == 64 and s._qstate.repack_epoch == 0
    s.refresh_tick(11.0, resample=True)
    assert s._qstate.repack_epoch == 0    # cap is already at the floor


# ------------------------------------------------- queueing-delay correction
def _chain_kb(dur_a=30.0, dur_b=5.0):
    def unit(name, image, durs, nxt):
        return UnitNode(name=name, backend=BackendSpec("docker", model=image),
                        duration=list(durs), next_counts=dict(nxt))
    units = {"a": unit("a", "img-a", [dur_a] * 20, {"b": 20}),
             "b": unit("b", "img-b", [dur_b] * 20, {"$end": 20})}
    return {"T": PDGraph("T", "a", units)}


def test_queue_stretch_delays_prewarm_trigger():
    """With queue_delay_correction on, an app observed to run at 2x wall
    per service second fires its downstream prewarm ~2x later; with the
    flag off the observation is ignored (bit-identical to the paper
    model)."""
    DOCKER_TP = 10.0
    fires = {}
    for corrected in (False, True):
        s = HermesScheduler(_chain_kb(dur_a=30.0), policy="gittins",
                            t_in=T_IN, t_out=T_OUT, mc_walkers=256, seed=3,
                            refresh=RefreshConfig(
                                mode="fused_delta", walker="pallas",
                                queue_delay_correction=corrected),
                            prewarm=True)
        s.on_arrival("x", "T", now=0.0)
        # task waited as long as it ran -> stretch EWMA pulls toward 2.0
        for _ in range(12):
            s.observe_queue_wait("x", wait_s=30.0, service_s=30.0)
        s.priorities(0.0)
        plan = s.take_prewarm_plan()
        by_key = dict(zip(plan.resource_keys, plan.fire_at))
        fires[corrected] = by_key["docker:img-b"]
    stretch = 2.0 - 0.7 ** 12                   # EWMA after 12 observations
    assert fires[False] == pytest.approx(30.0 - DOCKER_TP, abs=0.5)
    assert fires[True] == pytest.approx(stretch * 30.0 - DOCKER_TP, abs=1.0)
    assert fires[True] > fires[False] + 25.0


def test_store_arrival_rows_feed_the_plan(kb):
    """The batched plan is built from the store's persisted trigger rows
    (PrewarmPlan.from_store), not a side-channel: rows for walked slots are
    fresh and finite exactly where a plan entry exists."""
    s = HermesScheduler(_chain_kb(), policy="gittins", t_in=T_IN,
                        t_out=T_OUT, mc_walkers=256, seed=3,
                        refresh=RefreshConfig(mode="fused_delta",
                                              walker="pallas"),
                        prewarm=True)
    s.on_arrival("x", "T", now=0.0)
    s.priorities(0.0)
    plan = s.take_prewarm_plan()
    qs = s._qstate
    slot = qs.slot["x"]
    tab = s._prewarm_table()
    b = tab.classes.index("docker:img-b")
    assert qs.trig[slot, b] < ARRIVAL_NEVER / 2
    assert any(k == "docker:img-b" for k in plan.resource_keys)


# ----------------------------------------------- per-tick trigger retiming
def test_retrigger_delta_zero_is_bitwise_stable():
    """A walk-free tick with no intervening progress re-derives every
    trigger from the persisted arrival histograms at delta=0 — bit-identical
    to the walk-time triggers (one shared quantile code path)."""
    s = HermesScheduler(_chain_kb(), policy="gittins", t_in=T_IN,
                        t_out=T_OUT, mc_walkers=256, seed=3,
                        refresh=RefreshConfig(mode="fused_delta",
                                              walker="pallas"),
                        prewarm=True)
    s.on_arrival("x", "T", now=0.0)
    s.priorities(0.0)
    qs = s._qstate
    trig0, reach0 = qs.trig.copy(), qs.reach.copy()
    s.priorities(1.0)                       # no events: pure retrigger tick
    np.testing.assert_array_equal(qs.trig, trig0)
    np.testing.assert_array_equal(qs.reach, reach0)


def test_retrigger_tracks_elapsed_service():
    """With deterministic unit durations the ABSOLUTE fire time must stay
    put as the app executes: the relative trigger shrinks by exactly the
    attained service (the bucketized analogue of the legacy planner's
    ``tail - elapsed`` re-quantile), instead of freezing at walk time."""
    DOCKER_TP = 10.0
    s = HermesScheduler(_chain_kb(dur_a=30.0), policy="gittins", t_in=T_IN,
                        t_out=T_OUT, mc_walkers=256, seed=3,
                        refresh=RefreshConfig(mode="fused_delta",
                                              walker="pallas"),
                        prewarm=True)
    s.on_arrival("x", "T", now=0.0)
    s.priorities(0.0)
    plan0 = s.take_prewarm_plan()
    fire0 = dict(zip(plan0.resource_keys, plan0.fire_at))["docker:img-b"]
    assert fire0 == pytest.approx(30.0 - DOCKER_TP, abs=0.5)
    # 12 s of service later (progress does NOT dirty the slot -> no re-walk)
    s.on_progress("x", 12.0)
    before = s.apps["x"].refreshes
    s.priorities(12.0)
    assert s.apps["x"].refreshes == before
    plan1 = s.take_prewarm_plan()
    fire1 = dict(zip(plan1.resource_keys, plan1.fire_at))["docker:img-b"]
    assert fire1 == pytest.approx(fire0, abs=0.5)   # absolute time invariant
    # legacy closed form at the same elapsed service
    legacy = prewarm_trigger_time([30.0] * 20, unit_start=0.0, now=12.0,
                                  p_s=1.0, t_p=DOCKER_TP, K=0.5)
    assert fire1 == pytest.approx(legacy, abs=0.5)


def test_retrigger_conditions_reach_probability():
    """Arrivals the app has demonstrably outlived are falsified: once the
    attained service passes the early mode of a bimodal arrival
    distribution, the surviving reach mass (and the planner's p_reach)
    drops accordingly."""
    def unit(name, image, durs, nxt):
        return UnitNode(name=name, backend=BackendSpec("docker", model=image),
                        duration=list(durs), next_counts=dict(nxt))
    units = {"a": unit("a", "img-a", [10.0] * 10 + [50.0] * 10, {"b": 20}),
             "b": unit("b", "img-b", [5.0] * 20, {"$end": 20})}
    kb2 = {"T": PDGraph("T", "a", units)}
    s = HermesScheduler(kb2, policy="gittins", t_in=T_IN, t_out=T_OUT,
                        mc_walkers=512, seed=3,
                        refresh=RefreshConfig(mode="fused_delta",
                                              walker="pallas"),
                        prewarm=True, K=0.4)
    s.on_arrival("x", "T", now=0.0)
    s.priorities(0.0)
    qs = s._qstate
    tab = s._prewarm_table()
    b = tab.classes.index("docker:img-b")
    slot = qs.slot["x"]
    r0 = qs.reach[slot, b]
    assert r0 == pytest.approx(1.0, abs=0.05)
    s.on_progress("x", 20.0)          # outlived the 10 s mode entirely
    s.priorities(20.0)
    r1 = qs.reach[slot, b]
    assert r1 == pytest.approx(0.5, abs=0.1)
    assert r1 < r0 - 0.3
