"""Batched (whole-queue) composed refresh vs the same queue refreshed one
application at a time.

The batched refresh packs every PDGraph into shared padded unit tables and
derives per-(app, refresh) RNG keys by fold_in — exactly the chain the
single-app path (``priorities(now, [app_id])``) uses — so the two must
produce *identical* demand samples, histograms, and priority orderings, not
merely statistically similar ones.
"""
import numpy as np
import pytest

import jax

from repro.apps.suite import T_IN, T_OUT, build_knowledge_base
from repro.core.pdgraph import mc_service_samples_batch, pack_graphs
from repro.core.scheduler import HermesScheduler


@pytest.fixture(scope="module")
def kb():
    return build_knowledge_base(n_trials=60, seed=3)


def _filled_scheduler(kb, n_apps: int = 24,
                      policy: str = "gittins") -> HermesScheduler:
    s = HermesScheduler(kb, policy=policy, t_in=T_IN, t_out=T_OUT,
                        mc_walkers=32, seed=11)
    names = sorted(kb)
    for i in range(n_apps):
        aid = f"a{i:03d}"
        s.on_arrival(aid, names[i % len(names)], now=0.25 * i,
                     tenant=f"t{i % 4}", deadline=200.0 + 3.0 * i)
        s.on_progress(aid, 0.05 * i)
    return s


def _per_app(s: HermesScheduler, now: float, app_ids=None) -> dict:
    """The looped reference: every application refreshed and ranked alone,
    through the single-app ``priorities(now, [app_id])`` path."""
    ids = list(s._live) if app_ids is None else app_ids
    out = {}
    for i in ids:
        out.update(s.priorities(now, [i]))
    return out


def test_batched_walker_matches_per_graph_walk(kb):
    """mc_service_samples_batch == per-graph mc_service_samples bit-for-bit
    when fed the same fold_in key chain (padding must be invisible)."""
    packed = pack_graphs(kb, T_IN, T_OUT)
    base = jax.random.PRNGKey(3)
    names = sorted(kb)[:4]
    gi = np.asarray([packed.graph_index[n] for n in names], np.int32)
    batch = mc_service_samples_batch(
        packed, base, graph_idx=gi,
        start=packed.entry[gi],
        executed=np.zeros(len(names)),
        key_ids=np.arange(len(names), dtype=np.int32),
        refresh_ids=np.zeros(len(names), np.int32),
        n_walkers=64)
    for i, n in enumerate(names):
        key = jax.random.fold_in(jax.random.fold_in(base, i), 0)
        loop = kb[n].mc_service_samples(key, T_IN, T_OUT, n_walkers=64)
        np.testing.assert_array_equal(batch[i], loop)


def test_looped_and_batched_priorities_identical(kb):
    """Fixed seed: the per-app loop and the batched refresh produce the
    same ranks and therefore the same priority ordering."""
    r_loop = _per_app(_filled_scheduler(kb), 10.0)
    r_batch = _filled_scheduler(kb).priorities(10.0)
    assert sorted(r_loop) == sorted(r_batch)
    ids = sorted(r_loop)
    vl = np.asarray([r_loop[i] for i in ids])
    vb = np.asarray([r_batch[i] for i in ids])
    np.testing.assert_allclose(vl, vb, rtol=1e-6)
    assert np.array_equal(np.argsort(vl, kind="stable"),
                          np.argsort(vb, kind="stable"))


def test_modes_agree_after_unit_finish_with_refinement(kb):
    """Online refinement overrides flow through the batched override tables
    identically to the single-app walk's per-graph table patch."""
    out = {}
    for batched in (False, True):
        s = HermesScheduler(kb, t_in=T_IN, t_out=T_OUT, mc_walkers=32,
                            seed=7, refine=True)
        rank = s.priorities if batched else (lambda now: _per_app(s, now))
        for i in range(8):
            s.on_arrival(f"b{i}", "CG", now=float(i))
        rank(8.0)               # refresh everyone once
        for i in range(4):
            s.on_unit_finish(f"b{i}", "plan",
                             {"in": 500, "out": 280, "par": 1},
                             9.0, "generate")
        out[batched] = rank(10.0)
    ids = sorted(out[False])
    vl = np.asarray([out[False][i] for i in ids])
    vb = np.asarray([out[True][i] for i in ids])
    np.testing.assert_allclose(vl, vb, rtol=1e-6)


def test_priorities_subset_matches_full(kb):
    s = _filled_scheduler(kb)
    full = s.priorities(10.0)
    some = list(full)[:5]
    sub = s.priorities(10.0, app_ids=some)
    assert sorted(sub) == sorted(some)
    for i in some:
        assert sub[i] == pytest.approx(full[i])


def test_refresh_tick_resample_redraws_estimates(kb):
    s = _filled_scheduler(kb, n_apps=8)
    s.refresh_tick(5.0)
    before = {a.app_id: a.view.total_samples.copy()
              for a in s.apps.values()}
    refreshes = {a.app_id: a.refreshes for a in s.apps.values()}
    s.refresh_tick(6.0, resample=True)
    for a in s.apps.values():
        assert a.refreshes == refreshes[a.app_id] + 1
        assert not np.array_equal(a.view.total_samples, before[a.app_id])


def test_deadline_policy_modes_agree(kb):
    """The vectorized quantile path in hermes_ddl ranks like the per-app
    path."""
    r_loop = _per_app(_filled_scheduler(kb, policy="hermes_ddl"), 10.0)
    r_batch = _filled_scheduler(kb, policy="hermes_ddl").priorities(10.0)
    ids = sorted(r_loop)
    vl = np.asarray([r_loop[i] for i in ids])
    vb = np.asarray([r_batch[i] for i in ids])
    np.testing.assert_allclose(vl, vb, rtol=1e-6)
