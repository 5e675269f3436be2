"""Device-resident refresh pipeline (``fused_delta``) vs the composed
batched host path.

With ``walker="threefry"`` the device pipeline draws bit-identical demand
samples to the composed path (same fold_in chain through the same
`_walk_core`); the only divergence is float32-on-device vs float64-on-host
bucketization, so ranks must agree to float32 tolerance — including under
refinement overrides, nonzero attained service, and mixed graphs.  The
``walker="pallas"`` counter-RNG path is distributionally equivalent and is
covered by ordering-consistency and the KS tests in test_pdgraph_walk.py.
"""
import numpy as np
import pytest

from repro.apps.suite import T_IN, T_OUT, build_knowledge_base
from repro.core.arena import build_queue_state
from repro.core.refresh_config import RefreshConfig
from repro.core.scheduler import HermesScheduler


@pytest.fixture(scope="module")
def kb():
    return build_knowledge_base(n_trials=60, seed=3)


def _filled(kb, mode, walker="pallas", n_apps=24, refresh_kw=None, **kw):
    rc = RefreshConfig(mode=mode, walker=walker, **(refresh_kw or {}))
    s = HermesScheduler(kb, policy="gittins", t_in=T_IN, t_out=T_OUT,
                        mc_walkers=32, seed=11, refresh=rc, **kw)
    names = sorted(kb)
    for i in range(n_apps):
        aid = f"a{i:03d}"
        s.on_arrival(aid, names[i % len(names)], now=0.25 * i,
                     tenant=f"t{i % 4}", deadline=200.0 + 3.0 * i)
        s.on_progress(aid, 0.05 * i)       # nonzero attained service
    return s


def _vals(ranks):
    ids = sorted(ranks)
    return ids, np.asarray([ranks[i] for i in ids])


def test_fused_threefry_matches_composed_mixed_graphs(kb):
    """Acceptance: device ranks == composed ranks to float32 tolerance on a
    mixed-graph queue with attained service, same priority ordering."""
    r_comp = _filled(kb, "composed").priorities(10.0)
    r_fus = _filled(kb, "fused_delta", walker="threefry").priorities(10.0)
    ids_c, vc = _vals(r_comp)
    ids_f, vf = _vals(r_fus)
    assert ids_c == ids_f
    np.testing.assert_allclose(vc, vf, rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.argsort(vc, kind="stable"),
                          np.argsort(vf, kind="stable"))


def test_fused_threefry_matches_composed_with_overrides(kb):
    """Refinement overrides flow through the QueueState override tables
    identically to the composed per-tick table rebuild.  The full-walk
    fallback is off, so the device path re-walks only the four finished
    apps, as the composed path re-estimates only their views."""
    out = {}
    for mode, walker in (("composed", "pallas"), ("fused_delta", "threefry")):
        s = HermesScheduler(kb, t_in=T_IN, t_out=T_OUT, mc_walkers=32,
                            seed=7, refine=True,
                            refresh=RefreshConfig(mode=mode, walker=walker,
                                                  delta_full_threshold=1.0))
        for i in range(8):
            s.on_arrival(f"b{i}", "CG", now=float(i))
            s.on_progress(f"b{i}", 0.1 * i)
        s.priorities(8.0)
        for i in range(4):
            s.on_unit_finish(f"b{i}", "plan",
                             {"in": 500, "out": 280, "par": 1},
                             9.0, "generate")
        out[mode] = s.priorities(10.0)
    _, vc = _vals(out["composed"])
    _, vf = _vals(out["fused_delta"])
    np.testing.assert_allclose(vc, vf, rtol=1e-5, atol=1e-5)


def test_fused_subset_uses_cached_ranks(kb):
    """A subset priorities() call with no dirty slot walks nothing and
    returns the ranks of the last full refresh."""
    s = _filled(kb, "fused_delta")
    full = s.priorities(10.0)
    some = sorted(full)[:5]
    sub = s.priorities(10.0, app_ids=some)
    assert sorted(sub) == sorted(some)
    for i in some:
        assert sub[i] == pytest.approx(full[i])
    assert all(a.refreshes == 1 for a in s.apps.values())


def test_fused_subset_dispatch_matches_composed(kb):
    """A GENUINE subset device dispatch (dirty slots -> slots gather path)
    must rank like the composed path refreshing the same stale subset
    (same fold_in chain via walker='threefry')."""
    out = {}
    for mode, walker in (("composed", "pallas"), ("fused_delta", "threefry")):
        s = _filled(kb, mode, walker=walker)
        s.priorities(10.0)
        some = sorted(s._live)[:5]
        for i in some:
            s.apps[i].view = None          # composed: force re-estimation
            if s._qstate is not None:
                s._qstate.mark_dirty(i)    # device: force a re-walk
        out[mode] = s.priorities(10.0, app_ids=some)
        assert all(s.apps[i].refreshes == 2 for i in some)
    ids_c, vc = _vals(out["composed"])
    ids_f, vf = _vals(out["fused_delta"])
    assert ids_c == ids_f
    np.testing.assert_allclose(vc, vf, rtol=1e-5, atol=1e-5)


def test_fused_rank_only_reuse_between_ticks(kb):
    """Progress moves the rank but NOT the persisted histogram: the next
    priorities() re-ranks from the hist rows without re-walking anything."""
    s = _filled(kb, "fused_delta")
    full = s.priorities(10.0)
    before = {a.app_id: a.refreshes for a in s.apps.values()}
    s.on_progress("a000", 1.0)
    r2 = s.priorities(11.0)
    assert all(a.refreshes == before[a.app_id] for a in s.apps.values())
    assert r2["a000"] != full["a000"]


def test_fused_resample_redraws_and_never_ships_samples(kb):
    """Resampling is demand-driven on the device path: a tick re-walks the
    slots whose PDGraph position changed (here every slot), with fresh MC
    draws, and neither samples nor histogram rows reach the host."""
    s = _filled(kb, "fused_delta", n_apps=8)
    s.refresh_tick(5.0)
    refreshes = {a.app_id: a.refreshes for a in s.apps.values()}
    qs = s._qstate
    for a in s.apps.values():
        qs.mark_dirty(a.app_id)
    ranks1 = s.refresh_tick(6.0, resample=True)
    for a in s.apps.values():
        assert a.refreshes == refreshes[a.app_id] + 1
        assert a.view is None                      # device-resident
    assert qs.d_probs.shape == (qs.capacity, s.n_buckets)
    assert not isinstance(qs.d_probs, np.ndarray)
    for a in s.apps.values():
        qs.mark_dirty(a.app_id)
    ranks2 = s.refresh_tick(7.0, resample=True)
    _, v1 = _vals(ranks1)
    _, v2 = _vals(ranks2)
    assert not np.array_equal(v1, v2)              # fresh MC draws


def test_fused_pallas_orders_like_composed(kb):
    """The counter-RNG fused path is a different (equally valid) MC draw;
    with shared seeds the two orderings must still agree strongly — a rank
    correlation collapse means a walker defect, not MC noise."""
    r_comp = _filled(kb, "composed", n_apps=32).priorities(10.0)
    r_fus = _filled(kb, "fused_delta", walker="pallas",
                    n_apps=32).priorities(10.0)
    _, vc = _vals(r_comp)
    _, vf = _vals(r_fus)
    rc = np.argsort(np.argsort(vc))
    rf = np.argsort(np.argsort(vf))
    rho = np.corrcoef(rc, rf)[0, 1]                # Spearman
    assert rho > 0.9, rho


def test_queue_state_incremental_matches_rebuild(kb):
    """The incrementally-maintained QueueState (arrivals, progress, unit
    advance, overrides, retirement) must equal a from-scratch rebuild."""
    s = _filled(kb, "fused_delta", n_apps=12)
    s.priorities(5.0)                              # forces qstate creation
    s.on_unit_finish("a003", s.apps["a003"].current_unit,
                     {"in": 100, "out": 50, "par": 1, "dur": 1.0}, 6.0, None)
    s.on_progress("a001", 2.0)
    s.priorities(7.0)
    qs = s._qstate
    packed = s._packed_kb()
    qs2 = build_queue_state(packed, list(s._live.values()),
                            kb_token=s._packed[0])
    live = sorted(i for i in qs.ids if i is not None)
    assert live == sorted(i for i in qs2.ids if i is not None)
    perm = np.asarray([qs.slot[i] for i in live])
    perm2 = np.asarray([qs2.slot[i] for i in live])
    for name in ("graph_idx", "start", "executed", "attained",
                 "key_id", "refresh_id", "deadline", "stretch", "ov_counts"):
        np.testing.assert_array_equal(getattr(qs, name)[perm],
                                      getattr(qs2, name)[perm2],
                                      err_msg=name)
    so = qs2.ov_samples.shape[2]
    np.testing.assert_array_equal(qs.ov_samples[perm][:, :, :so],
                                  qs2.ov_samples[perm2])


def test_fused_ranks_stay_aligned_after_retirement(kb):
    """Retiring an app leaves holes in the slot arena, diverging slot order
    from _live insertion order; the full-queue device refresh must keep
    each rank attached to ITS app (regression: ranks were zipped across
    orders)."""
    out = {}
    for mode, walker in (("composed", "pallas"), ("fused_delta", "threefry")):
        s = _filled(kb, mode, walker=walker, n_apps=12)
        s.priorities(10.0)
        s.on_app_complete("a001")          # holes in the arena
        s.on_app_complete("a004")
        out[mode] = s.refresh_tick(12.0)
    ids_c, vc = _vals(out["composed"])
    ids_f, vf = _vals(out["fused_delta"])
    assert ids_c == ids_f and "a001" not in ids_c
    np.testing.assert_allclose(vc, vf, rtol=1e-5, atol=1e-5)


def test_fused_spill_counter_starts_clean(kb):
    s = _filled(kb, "fused_delta", n_apps=16)
    s.refresh_tick(5.0, resample=True)
    assert s.fused_spill == 0


def test_fused_no_phantom_spill_from_queue_padding(kb):
    """Padding rows (20 apps pad to 32) walk as garbage-but-valid apps;
    their walkers must start absorbed so they neither occupy compaction
    capacity nor surface as phantom spill."""
    s = _filled(kb, "fused_delta", n_apps=20, compact_after=4,
                compact_shrink=4)
    s.refresh_tick(5.0, resample=True)
    assert s.fused_spill == 0
