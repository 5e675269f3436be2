"""Array-native event engine vs the seed's heap engine.

The contract (``repro.serving.events``): for the same pushes, both event
queues emit IDENTICAL ``(t, batch)`` sequences — same timestamps, same
micro-batch contents, same within-batch order — and both waiting queues pop
in identical order across pushes, pops, and full rank rebuilds.  On top of
that, ``ClusterSim`` with ``engine="calendar"`` must reproduce the heap
engine's ``SimResult`` *exactly* (completion order, ACTs, makespan, cache
stats) on randomized open-arrival traces, including simultaneous-event
bursts and mid-run arena repacks.

The heap engine is deprecated (``SimConfig(engine="heap")`` warns, and the
default tier only checks that the warning fires); the full heap/calendar
equivalence suite runs on the slow tier (``-m slow``) until the heap loop
is removed.

Also here: the RefreshConfig construction surface (its validation, both
entry points' defaults, the retired per-field spellings refused) and
``PrewarmPlan.merge``.
"""
import warnings

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.apps.suite import T_IN, T_OUT, build_knowledge_base
from repro.apps.workload import make_open_workload, make_workload
from repro.core.prewarm import PrewarmPlan
from repro.core.refresh_config import RefreshConfig
from repro.core.scheduler import HermesScheduler
from repro.serving.events import (ArrayWaitQueue, CalendarEventQueue,
                                  HeapEventQueue, HeapWaitQueue,
                                  make_event_queue, make_wait_queue)
from repro.serving.simulator import ClusterSim, SimConfig, run_sim

_KB = None


def _kb():
    """Module-lazy KB (hypothesis-driven tests can't take fixtures)."""
    global _KB
    if _KB is None:
        _KB = build_knowledge_base(n_trials=40, seed=3)
    return _KB


# ---------------------------------------------------------------- event queue

def _drive_both(rng, n_rounds=40):
    """Random interleaving of pushes and drains, exercising: timestamp ties,
    pushes into the bucket currently being drained (the late-buffer path),
    wheel-crossing gaps, and many-runs compaction.  Asserts the two engines
    emit identical batch sequences."""
    h, c = HeapEventQueue(), CalendarEventQueue(bucket_s=1.0)
    # offsets are multiples of 0.25 so exact-tie timestamps are common
    now, uid = 0.0, 0
    for _ in range(int(rng.integers(1, 5))):
        t = float(rng.integers(0, 16)) * 0.25
        h.push(t, "e", uid)
        c.push(t, "e", uid)
        uid += 1
    for _ in range(n_rounds):
        if len(h) == 0:
            break
        th, bh = h.next_batch()
        tc, bc = c.next_batch()
        assert th == tc
        assert bh == bc
        now = th
        # follow-up pushes at t >= now: 0 (re-tie, same bucket), small
        # (same/next bucket), large (skips buckets)
        for _ in range(int(rng.integers(0, 4))):
            dt = float(rng.choice([0.0, 0.25, 0.5, 1.0, 3.25, 7.0]))
            h.push(now + dt, "e", uid)
            c.push(now + dt, "e", uid)
            uid += 1
    while len(h):
        assert h.next_batch() == c.next_batch()
    assert len(c) == 0


@pytest.mark.slow
@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_calendar_matches_heap_event_order(seed):
    _drive_both(np.random.default_rng(seed))


def test_calendar_same_timestamp_across_late_pushes_keeps_push_order():
    """Events pushed mid-drain at an already-seen timestamp must drain in
    push order behind the earlier pushes (run-creation order)."""
    c = CalendarEventQueue(bucket_s=10.0)
    for i in range(3):
        c.push(1.0, "a", i)
    t, batch = c.next_batch()
    assert (t, batch) == (1.0, [("a", 0), ("a", 1), ("a", 2)])
    c.push(2.0, "b", 0)
    c.push(2.0, "b", 1)       # same bucket: late buffer
    assert c.next_batch() == (2.0, [("b", 0), ("b", 1)])
    # interleave: settled run holds t=3 and t=5; late pushes add more t=3
    c.push(3.0, "c", 0)
    c.push(5.0, "d", 0)
    c.push(3.0, "c", 1)
    assert c.next_batch() == (3.0, [("c", 0), ("c", 1)])
    assert c.next_batch() == (5.0, [("d", 0)])
    assert len(c) == 0


def test_calendar_run_compaction_preserves_order():
    """> _MAX_RUNS late-settle cycles inside one bucket trigger compaction;
    order must survive the merge."""
    c = CalendarEventQueue(bucket_s=1e9)      # everything in one bucket
    c.push(0.0, "seed", None)
    c.next_batch()
    expect = []
    for k in range(3 * CalendarEventQueue._MAX_RUNS):
        t = 10.0 + k
        c.push(t, "e", k)         # each drain settles a fresh run
        expect.append((t, [("e", k)]))
        if k % 3 == 0:
            c.push(t, "tie", k)   # same-t tie within the same run
            expect[-1][1].append(("tie", k))
        got = c.next_batch()
        assert got == expect[-1]


def test_event_queue_factory():
    assert isinstance(make_event_queue("heap"), HeapEventQueue)
    assert isinstance(make_event_queue("calendar", bucket_s=2.0),
                      CalendarEventQueue)
    with pytest.raises(ValueError, match="unknown sim engine"):
        make_event_queue("wheel-of-fortune")
    with pytest.raises(ValueError, match="positive"):
        CalendarEventQueue(bucket_s=0.0)


# --------------------------------------------------------------- wait queues

class _T:
    __slots__ = ("submitted", "task_id", "ai")

    def __init__(self, submitted, task_id, ai):
        self.submitted, self.task_id, self.ai = submitted, task_id, ai


@pytest.mark.slow
@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_array_wait_queue_matches_heap(seed):
    """Random push/pop/rebuild interleavings: identical pop order, with
    rebuilds re-keying r0 from a mutating rank column (stale-key semantics
    shared by both: keys snapshot at push, refresh at rebuild)."""
    rng = np.random.default_rng(seed)
    n_apps = int(rng.integers(2, 8))
    ranks = rng.uniform(0, 10, n_apps)
    hq, aq = HeapWaitQueue(), ArrayWaitQueue()
    uid = 0
    for step in range(int(rng.integers(5, 60))):
        op = rng.uniform()
        if op < 0.55:
            ai = int(rng.integers(n_apps))
            t = _T(float(rng.integers(0, 8)) * 0.5, uid, ai)
            uid += 1
            key = (float(ranks[ai]), t.submitted, t.task_id)
            hq.push(key, t, ai)
            aq.push(key, t, ai)
        elif op < 0.85:
            assert len(hq) == len(aq)
            if len(hq):
                assert hq.peek_key() == tuple(map(float, aq.peek_key()))
                assert hq.pop() is aq.pop()
        else:
            ranks = rng.uniform(0, 10, n_apps)       # rank refresh
            hq.rebuild(lambda t: (float(ranks[t.ai]), t.submitted, t.task_id))
            aq.rebuild(ranks)
    while len(hq):
        assert len(aq) and hq.pop() is aq.pop()
    assert len(aq) == 0


def test_array_wait_queue_task_level_rebuild_keeps_keys():
    """rank_of=None (task-level policies): rebuild resorts but keeps the
    stored keys verbatim."""
    aq = ArrayWaitQueue()
    ts = [_T(float(i % 3), i, -1) for i in range(7)]
    for t in ts:
        aq.push((t.submitted, float(t.task_id), 0.0), t, -1)
    aq.rebuild(None)
    order = [aq.pop() for _ in range(len(aq))]
    assert order == sorted(ts, key=lambda t: (t.submitted, t.task_id))
    assert isinstance(make_wait_queue("heap"), HeapWaitQueue)
    assert isinstance(make_wait_queue("calendar"), ArrayWaitQueue)
    with pytest.raises(ValueError):
        make_wait_queue("nope")


# ------------------------------------------------------- full-sim equivalence

def _assert_equivalent(a, b):
    assert a.completion_order == b.completion_order
    assert a.acts == b.acts
    assert a.makespan == b.makespan
    assert a.policy_calls == b.policy_calls
    assert a.cache_stats == b.cache_stats
    assert a.stall_stats == b.stall_stats
    assert a.dsr == b.dsr


def _heap_cfg(**cfg_kw):
    """Build the deprecated-engine config without tripping ``-W error``
    runs — the deprecation itself is pinned by
    ``test_heap_engine_deprecated``."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return SimConfig(engine="heap", **cfg_kw)


def _run_both(insts, **cfg_kw):
    out = []
    for eng in ("heap", "calendar"):
        cfg = (_heap_cfg(**cfg_kw) if eng == "heap"
               else SimConfig(engine=eng, **cfg_kw))
        out.append(run_sim(_kb(), insts, cfg))
    return out


def test_heap_engine_deprecated():
    """engine="heap" is a one-release oracle: constructing it warns and
    names the supported engine."""
    with pytest.warns(DeprecationWarning, match="calendar"):
        cfg = SimConfig(engine="heap")
    assert cfg.engine == "heap"          # still constructs (oracle tier)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        SimConfig()                      # the default engine never warns


@pytest.mark.slow
@settings(max_examples=4, deadline=None)
@given(st.integers(min_value=0, max_value=10**4),
       st.sampled_from(["gittins", "fcfs_app", "vtc", "hermes_ddl",
                        "fcfs_req"]))
def test_engines_bit_equivalent_on_open_arrivals(seed, policy):
    """Randomized bursty open-arrival traces: the calendar engine's
    SimResult matches the heap engine's exactly."""
    insts = make_open_workload(60.0, t_in=T_IN, t_out=T_OUT, rate_per_s=0.5,
                               process="gamma", cv=2.5, seed=seed,
                               with_deadlines=True, max_apps=24)
    if not insts:
        return
    a, b = _run_both(insts, policy=policy, mc_walkers=16, seed=seed % 7,
                     n_llm_slots=4, n_docker_slots=6, n_dnn_slots=2)
    _assert_equivalent(a, b)


@pytest.mark.slow
def test_engines_bit_equivalent_on_simultaneous_bursts():
    """Arrivals quantized to whole seconds: large same-timestamp
    micro-batches (batch admission + shared drain helper) stay equivalent."""
    insts = make_workload(32, 6.0, seed=11, t_in=T_IN, t_out=T_OUT,
                          with_deadlines=True)
    for i in insts:
        i.arrival = float(int(i.arrival))     # force exact ties
    a, b = _run_both(insts, policy="gittins", mc_walkers=16, seed=3,
                     n_llm_slots=4)
    _assert_equivalent(a, b)


@pytest.mark.slow
def test_engines_bit_equivalent_across_midrun_repack():
    """A trace long enough that the slot arena shrink-repacks mid-run
    (slot renumbering + device-row remap) on the fused_delta path."""
    insts = make_workload(150, 4.0, seed=9, t_in=T_IN, t_out=T_OUT)
    sims = []
    for eng in ("heap", "calendar"):
        cfg_kw = dict(mc_walkers=16, seed=2, n_llm_slots=8)
        cfg = (_heap_cfg(**cfg_kw) if eng == "heap"
               else SimConfig(engine=eng, **cfg_kw))
        sim = ClusterSim(_kb(), cfg)
        sims.append((sim, sim.run(insts)))
    (sa, a), (sb, b) = sims
    assert sa.sched._qstate.repack_epoch >= 1    # the repack actually fired
    assert sa.sched._qstate.repack_epoch == sb.sched._qstate.repack_epoch
    _assert_equivalent(a, b)


@pytest.mark.slow
def test_engines_bit_equivalent_with_posterior_on_drift_trace():
    """Seeded drift trace with online posterior learning ON: both engines
    drain identical micro-batches, so they fold identical observation
    streams — completion order, stats, accumulated posterior counts, and
    the device-resident posterior rows of every live slot all match."""
    from repro.apps.workload import make_drift_workload
    from repro.core.posterior import PosteriorConfig
    insts = make_drift_workload(90.0, t_in=T_IN, t_out=T_OUT, shift_at=30.0,
                                rate_per_s=0.4, seed=7)
    assert any(i.app_id.startswith("drift") for i in insts)
    sims = []
    for eng in ("heap", "calendar"):
        cfg_kw = dict(mc_walkers=16, seed=2, n_llm_slots=4,
                      posterior=PosteriorConfig())
        cfg = (_heap_cfg(**cfg_kw) if eng == "heap"
               else SimConfig(engine=eng, **cfg_kw))
        sim = ClusterSim(_kb(), cfg)
        sims.append((sim, sim.run(list(insts))))
    (sa, a), (sb, b) = sims
    _assert_equivalent(a, b)
    # same observation stream folded on both sides
    n_obs = sa.sched._post_state.n_observations()
    assert n_obs > 0
    assert n_obs == sb.sched._post_state.n_observations()
    # device-resident posterior rows agree slot-for-slot for live apps
    qa, qb = sa.sched._qstate, sb.sched._qstate
    assert set(qa.slot) == set(qb.slot)
    for aid in qa.slot:
        ra = qa.posterior_rows(np.asarray([qa.slot[aid]]))[0]
        rb = qb.posterior_rows(np.asarray([qb.slot[aid]]))[0]
        np.testing.assert_array_equal(ra, rb, err_msg=aid)


# ------------------------------------------------- RefreshConfig round-trips

def test_refresh_config_validation():
    for mode in ("warp", "fused", "looped"):
        with pytest.raises(ValueError, match="unknown refresh mode"):
            RefreshConfig(mode=mode)
    with pytest.raises(ValueError, match="walker"):
        RefreshConfig(walker="xorshift")
    with pytest.raises(ValueError, match="requires mode='fused_delta'"):
        RefreshConfig(mode="composed", mesh_shards=2)
    with pytest.raises(ValueError, match="power of two"):
        RefreshConfig(mesh_shards=3)
    with pytest.raises(ValueError, match="delta_full_threshold"):
        RefreshConfig(delta_full_threshold=-0.5)
    rc = RefreshConfig()
    assert (rc.mode, rc.walker) == ("fused_delta", "pallas")


def test_legacy_kwargs_are_retired():
    """Every retired per-field refresh kwarg is an unexpected keyword on
    both public construction surfaces: the field lives on RefreshConfig."""
    kb = _kb()
    for kw in (dict(batched=False), dict(mode="fused"),
               dict(walker="threefry"), dict(mesh_shards=1),
               dict(delta_full_threshold=0.25),
               dict(queue_delay_correction=True)):
        with pytest.raises(TypeError, match="unexpected keyword"):
            HermesScheduler(kb, **kw)
    for kw in (dict(refresh_mode="fused"), dict(walker="threefry"),
               dict(mesh_shards=1), dict(queue_delay_correction=True)):
        with pytest.raises(TypeError, match="unexpected keyword"):
            SimConfig(**kw)


def test_scheduler_accepts_refresh_config_and_keeps_bare_default():
    kb = _kb()
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        s = HermesScheduler(kb, refresh=RefreshConfig(mode="fused_delta",
                                                      walker="threefry"))
        assert (s.mode, s.walker) == ("fused_delta", "threefry")
        assert s.refresh_config.mode == "fused_delta"
        # a bare scheduler refreshes on the composed host path
        assert HermesScheduler(kb).mode == "composed"
    with pytest.raises(TypeError, match="unexpected keyword"):
        HermesScheduler(kb, mode="fused", walker="threefry")


def test_simconfig_accepts_refresh_config_and_rejects_legacy():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        cfg = SimConfig(refresh=RefreshConfig(mode="composed"))
        assert cfg.refresh.mode == "composed"
        assert SimConfig().refresh == RefreshConfig()     # sim default
    with pytest.raises(TypeError, match="unexpected keyword"):
        SimConfig(refresh_mode="fused", walker="threefry",
                  queue_delay_correction=True)
    with pytest.raises(ValueError, match="unknown sim engine"):
        SimConfig(engine="abacus")


# ------------------------------------------------------ PrewarmPlan.merge

def test_prewarm_plan_merge_dedups_and_prunes():
    """``merge`` keeps one row per (app, class) with the newer plan's
    trigger winning, drops apps that are no longer live, and carries each
    row's unit ("*" where the plan names none)."""
    p1 = PrewarmPlan(app_ids=["a", "c"], resource_keys=["kv:x", "kv:z"],
                     kinds=["kv", "kv"], fire_at=np.asarray([5.0, 7.0]),
                     p_reach=np.asarray([0.9, 0.7]))
    p2 = PrewarmPlan(app_ids=["b", "a"], resource_keys=["kv:y", "kv:x"],
                     kinds=["kv", "kv"], fire_at=np.asarray([6.0, 4.0]),
                     p_reach=np.asarray([0.8, 0.95]),
                     units=["plan", "gen"])
    m = p1.merge(p2, lambda a: a != "c")
    assert m.app_ids == ["a", "b"]
    assert m.resource_keys == ["kv:x", "kv:y"]
    np.testing.assert_array_equal(m.fire_at, [4.0, 6.0])   # newer wins
    np.testing.assert_array_equal(m.p_reach,
                                  np.asarray([0.95, 0.8], np.float32))
    assert [m.unit_of(i) for i in range(2)] == ["gen", "plan"]
    assert p1.merge(p2, lambda a: True).unit_of(1) == "*"       # c
