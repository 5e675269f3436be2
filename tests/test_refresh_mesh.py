"""Mesh-sharded refresh backbone: bit-identity, churn, and guard rails.

The acceptance contract for the sharded arena (PR 5): for the same
slot→shard placement, a mesh tick at ANY shard count produces bit-identical
ranks, histogram rows, triage scalars and merged PrewarmPlan to the
single-arena delta path — walker RNG streams are keyed by the app, not by
batch position or shard, and every pipeline stage is per-row math.

Shard counts above the visible device count skip; CI's multi-device leg
runs the full 1/2/8 matrix under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import numpy as np
import pytest

import jax

from repro.apps.suite import T_IN, T_OUT, build_knowledge_base
from repro.core.posterior import PosteriorConfig
from repro.core.refresh_config import RefreshConfig
from repro.core.refresh_mesh import RefreshMesh
from repro.core.scheduler import HermesScheduler

MC = 32


def _needs(n):
    return pytest.mark.skipif(
        jax.device_count() < n,
        reason=f"needs {n} devices (XLA_FLAGS="
               f"--xla_force_host_platform_device_count={n})")


SHARD_PARAMS = [pytest.param(n, marks=_needs(n)) for n in (1, 2, 8)]


@pytest.fixture(scope="module")
def kb():
    return build_knowledge_base(n_trials=60, seed=3)


def _filled(kb, mesh_shards=None, policy="gittins", prewarm=False,
            walker="pallas", n_apps=24, posterior=None):
    s = HermesScheduler(kb, policy=policy, t_in=T_IN, t_out=T_OUT,
                        mc_walkers=MC, seed=11, prewarm=prewarm,
                        posterior=posterior,
                        refresh=RefreshConfig(mode="fused_delta",
                                              walker=walker,
                                              mesh_shards=mesh_shards))
    names = sorted(kb)
    for i in range(n_apps):
        aid = f"a{i:03d}"
        s.on_arrival(aid, names[i % len(names)], now=0.25 * i,
                     tenant=f"t{i % 4}", deadline=200.0 + 3.0 * i)
        s.on_progress(aid, 0.05 * i)
    return s


def _churn(s, kb, t):
    """Progress + unit transition + retirement + admission — every dirty/
    rank-dirty pathway, landing on different shards (consecutive slot ids
    have different residues)."""
    s.on_progress("a003", 1.0)
    s.on_unit_start("a005", s.apps["a005"].current_unit, t)
    if "a007" in s._live:
        s.on_app_complete("a007")
    if f"new{int(t)}" not in s.apps:
        s.on_arrival(f"new{int(t)}", sorted(kb)[0], now=t)


def _vals(ranks):
    ids = sorted(ranks)
    return ids, np.asarray([ranks[i] for i in ids])


def _obs(s, t):
    """Posterior-update interleaving: the explicit observation feed plus the
    self-observing ``on_unit_finish`` path (a unit transition, so the slot
    also goes dirty and re-walks with the new posterior row next tick)."""
    u2 = s.apps["a002"].current_unit
    if u2 is not None:
        s.observe_unit_completion("a002", u2, 3.5 + 0.25 * t,
                                  wall_s=5.0 + 0.25 * t)
        s.observe_branch_taken("a002", u2, None)
    u6 = s.apps["a006"].current_unit
    if u6 is not None:
        s.on_unit_finish("a006", u6, {"dur": 2.0 + t}, t, u6)


@pytest.mark.parametrize("n_shards", SHARD_PARAMS)
@pytest.mark.parametrize("walker", ["pallas", "threefry"])
def test_mesh_bit_identical_to_single_shard(kb, n_shards, walker):
    """Ranks AND the persisted per-app histogram rows match the single-arena
    delta path to the BIT across ticks with live churn."""
    a = _filled(kb, None, walker=walker)
    b = _filled(kb, n_shards, walker=walker)
    for t in (10.0, 11.0, 12.0):
        ra = a.refresh_tick(t, resample=True)
        rb = b.refresh_tick(t, resample=True)
        ids_a, va = _vals(ra)
        ids_b, vb = _vals(rb)
        assert ids_a == ids_b
        np.testing.assert_array_equal(va, vb,
                                      err_msg=f"shards={n_shards} t={t}")
        _churn(a, kb, t)
        _churn(b, kb, t)
    assert b.fused_spill == 0
    qa, qb = a._qstate, b._qstate
    pa = np.asarray(qa.d_probs)
    pb = np.asarray(qb.d_probs)
    for aid, sa in qa.slot.items():
        ra_ = pa[qa.device_rows(np.asarray([sa]))[0]]
        rb_ = pb[qb.device_rows(np.asarray([qb.slot[aid]]))[0]]
        np.testing.assert_array_equal(ra_, rb_, err_msg=aid)


@pytest.mark.parametrize("n_shards", SHARD_PARAMS)
def test_mesh_triage_and_plan_identical(kb, n_shards):
    """Composite-policy triage scalars and the merged cross-shard
    PrewarmPlan match the single-arena path exactly."""
    a = _filled(kb, None, policy="hermes_ddl", prewarm=True)
    b = _filled(kb, n_shards, policy="hermes_ddl", prewarm=True)
    for t in (10.0, 11.0):
        ra = a.refresh_tick(t, resample=True)
        rb = b.refresh_tick(t, resample=True)
        _, va = _vals(ra)
        _, vb = _vals(rb)
        np.testing.assert_array_equal(va, vb)
        pa, pb = a.take_prewarm_plan(), b.take_prewarm_plan()
        ka = sorted(zip(pa.app_ids, pa.resource_keys, pa.fire_at,
                        pa.p_reach))
        kb_ = sorted(zip(pb.app_ids, pb.resource_keys, pb.fire_at,
                         pb.p_reach))
        assert ka == kb_
        _churn(a, kb, t)
        _churn(b, kb, t)
    qa, qb = a._qstate, b._qstate
    for aid, sa in qa.slot.items():
        sb = qb.slot[aid]
        for row in ("sup", "opt", "mean"):
            assert getattr(qa, row)[sa] == getattr(qb, row)[sb], (aid, row)


@pytest.mark.parametrize("n_shards", [pytest.param(n, marks=_needs(n))
                                      for n in (2, 8)])
def test_mesh_churn_lands_on_different_shards(kb, n_shards):
    """Mid-run admits/retires hit different shards (residue placement) and
    the tick keeps every rank attached to the right application."""
    s = _filled(kb, n_shards, n_apps=12)
    s.priorities(10.0)
    qs = s._qstate
    s.on_app_complete("a001")
    s.on_app_complete("a006")
    s.on_arrival("x0", sorted(kb)[0], now=11.0)
    s.on_arrival("x1", sorted(kb)[1 % len(kb)], now=11.0)
    r = s.priorities(11.0)
    shards = {qs.slot["x0"] % n_shards, qs.slot["x1"] % n_shards}
    assert len(shards) == 2                    # spread, not piled on shard 0
    assert "a001" not in r and "a006" not in r
    assert "x0" in r and "x1" in r
    assert np.isfinite(list(r.values())).all()
    assert s.apps["x0"].refreshes == 1         # walked before first consume
    # progressed-only apps get re-ranked without a walk, shard-locally
    before = {a.app_id: a.refreshes for a in s.apps.values() if not a.done}
    s.on_progress("a003", 2.0)
    r2 = s.priorities(12.0)
    assert r2["a003"] != r["a003"]
    assert all(a.refreshes == before[a.app_id]
               for a in s.apps.values() if not a.done)


@pytest.mark.parametrize("n_shards", SHARD_PARAMS)
def test_mesh_event_path_subset_updates_full_tick_ranks(kb, n_shards):
    """An event-path subset refresh (priorities with app_ids) re-walks the
    touched slot and drains its marks; the NEXT full tick must serve the
    post-event rank, not a stale cache entry — and must still match the
    single-arena path bitwise (regression: the incremental rank dict was
    only updated on full ticks)."""
    a = _filled(kb, None)
    b = _filled(kb, n_shards)
    for s in (a, b):
        s.refresh_tick(10.0, resample=True)
    for s in (a, b):
        s.on_unit_start("a004", s.apps["a004"].current_unit, 10.5)
        s.priorities(10.5, app_ids=["a004"])     # simulator event micro-batch
    ra = a.refresh_tick(11.0, resample=True)
    rb = b.refresh_tick(11.0, resample=True)
    ids_a, va = _vals(ra)
    ids_b, vb = _vals(rb)
    assert ids_a == ids_b
    np.testing.assert_array_equal(va, vb)


def test_mesh_requires_delta_mode(kb):
    with pytest.raises(ValueError, match="fused_delta"):
        HermesScheduler(kb, policy="gittins",
                        refresh=RefreshConfig(mode="composed",
                                              mesh_shards=1))


def test_mesh_shard_count_guards(kb):
    with pytest.raises(ValueError, match="power of two"):
        RefreshMesh(3)
    if jax.device_count() < 16:
        with pytest.raises(ValueError, match="devices"):
            RefreshMesh(16)


def test_mesh_schedule_respects_disabled_compaction():
    """compact_shrink=1 / compact_after=0 are the legacy off switches; the
    mesh's multi-stage schedule must keep compaction OFF, not bolt a live
    tail stage onto a disabled first stage."""
    from repro.core.refresh_mesh import _mesh_schedule
    assert _mesh_schedule(16, 1, 1 << 20) == ((16, 1),)
    assert _mesh_schedule(0, 4, 1 << 20) == ((0, 4),)
    assert _mesh_schedule(16, 4, 1 << 20) == ((12, 4), (28, 16), (44, 64))
    assert _mesh_schedule(16, 4, 1024) == ((16, 4),)
    assert _mesh_schedule(8, 2, 1 << 20) == ((8, 2), (16, 8))


def test_mesh_replicated_cache_is_bounded():
    """Superseded KB/prewarm tables must not stay pinned on every device:
    id-keyed replicated entries evict past the cap (zeros placeholders are
    shared across generations and exempt)."""
    mesh = RefreshMesh(1)
    mesh.zeros_rows("gi", 0, np.int32)
    for i in range(RefreshMesh._REP_CAP + 20):
        mesh.replicated(np.full(4, i, np.float32))
    idk = [k for k in mesh._rep if not (isinstance(k, tuple)
                                        and k[0] == "zeros")]
    assert len(idk) <= RefreshMesh._REP_CAP
    assert any(isinstance(k, tuple) and k[0] == "zeros" for k in mesh._rep)


@pytest.mark.parametrize("n_shards", SHARD_PARAMS)
@pytest.mark.parametrize("walker", ["pallas", "threefry"])
def test_mesh_posterior_bit_identical_to_single_shard(kb, n_shards, walker):
    """Online posterior learning under the mesh: with identical churn AND
    identical observation streams, a sharded tick's ranks and the
    device-resident posterior rows match the single-arena delta path to the
    BIT — the posterior gather is per-row math like every other mirror."""
    a = _filled(kb, None, walker=walker, posterior=PosteriorConfig())
    b = _filled(kb, n_shards, walker=walker, posterior=PosteriorConfig())
    for t in (10.0, 11.0, 12.0, 13.0):
        ra = a.refresh_tick(t, resample=True)
        rb = b.refresh_tick(t, resample=True)
        ids_a, va = _vals(ra)
        ids_b, vb = _vals(rb)
        assert ids_a == ids_b
        np.testing.assert_array_equal(va, vb,
                                      err_msg=f"shards={n_shards} t={t}")
        if t < 13.0:                      # last tick scatters the final batch
            _churn(a, kb, t)
            _churn(b, kb, t)
            _obs(a, t)
            _obs(b, t)
    assert a._post_state.n_observations() > 0
    assert (a._post_state.n_observations()
            == b._post_state.n_observations())
    qa, qb = a._qstate, b._qstate
    for aid, sa in qa.slot.items():
        ra_ = qa.posterior_rows(np.asarray([sa]))[0]
        rb_ = qb.posterior_rows(np.asarray([qb.slot[aid]]))[0]
        np.testing.assert_array_equal(ra_, rb_, err_msg=aid)
    # the observed-and-transitioned app actually carries a non-zero row
    # (the comparison above is not vacuously all-zeros)
    assert qa.posterior_rows(np.asarray([qa.slot["a006"]]))[0].sum() > 0


@pytest.mark.parametrize("n_shards", SHARD_PARAMS)
def test_mesh_repack_remaps_posterior_rows(kb, n_shards):
    """A shrink repack renumbers slots and remaps device rows across shard
    blocks; the posterior rows must ride the same remap — every survivor
    keeps its rank AND its scattered posterior row bitwise, without a
    re-walk."""
    s = _filled(kb, n_shards, n_apps=96, posterior=PosteriorConfig())
    for aid in ("a090", "a091", "a092"):
        u = s.apps[aid].current_unit
        s.observe_unit_completion(aid, u, 7.5)
        s.observe_branch_taken(aid, u, None)
        s.on_requeue(aid, 9.0)            # dirty: the walk scatters the row
    r1 = s.refresh_tick(10.0, resample=True)
    qs = s._qstate
    cap0, epoch0 = qs.capacity, qs.repack_epoch
    for i in range(88):
        s.on_app_complete(f"a{i:03d}")
    survivors = [a.app_id for a in s.apps.values() if not a.done]
    post_pre = {aid: qs.posterior_rows(
        np.asarray([qs.slot[aid]]))[0].copy() for aid in survivors}
    assert any(row.sum() > 0 for row in post_pre.values())
    s._mesh_ranks = None
    r2 = s.refresh_tick(11.0, resample=True)
    assert qs.repack_epoch == epoch0 + 1 and qs.capacity < cap0
    for aid in survivors:
        assert r2[aid] == r1[aid], aid
        row = qs.posterior_rows(np.asarray([qs.slot[aid]]))[0]
        np.testing.assert_array_equal(row, post_pre[aid], err_msg=aid)


@pytest.mark.parametrize("n_shards", SHARD_PARAMS)
def test_mesh_survives_repack_epoch(kb, n_shards):
    """A shrink repack (slot ids renumbered, device rows remapped across
    shard blocks) preserves every surviving app's rank without a re-walk."""
    s = _filled(kb, n_shards, n_apps=96)
    r1 = s.refresh_tick(10.0, resample=True)
    qs = s._qstate
    cap0, epoch0 = qs.capacity, qs.repack_epoch
    for i in range(88):
        s.on_app_complete(f"a{i:03d}")
    survivors = [a.app_id for a in s.apps.values() if not a.done]
    before = {aid: s.apps[aid].refreshes for aid in survivors}
    probs = np.asarray(qs.d_probs)
    hist_pre = {aid: probs[qs.device_rows(
        np.asarray([qs.slot[aid]]))[0]].copy() for aid in survivors}
    s._mesh_ranks = None           # force the dict rebuild off store rows
    r2 = s.refresh_tick(11.0, resample=True)
    assert qs.repack_epoch == epoch0 + 1 and qs.capacity < cap0
    probs = np.asarray(qs.d_probs)
    for aid in survivors:
        assert r2[aid] == r1[aid], aid         # rank survived the remap
        assert s.apps[aid].refreshes == before[aid]   # ...without a walk
        row = probs[qs.device_rows(np.asarray([qs.slot[aid]]))[0]]
        np.testing.assert_array_equal(row, hist_pre[aid], err_msg=aid)
