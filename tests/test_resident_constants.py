"""The single-arena refresh dispatch keeps its constants on the device.

The prewarm tables, K and the placeholders are uploaded once, on the
first dispatch that needs them (the tables once per ``PrewarmTable``
object, so a KB repack uploads its new table by itself); the per-dispatch
row arrays reach the jit as host arrays.  Pinned here:

* a second dispatch of the same shape uploads no constant, and a new
  table object uploads its two arrays once;
* the dispatch's ranks, arena rows and trigger rows are bit for bit those
  of ``_delta_pipeline`` called on the same state with every argument
  uploaded by ``jnp.asarray`` and every constant built afresh;
* the mesh path still places the resident placeholders on its mesh.
"""
import dataclasses
from collections import OrderedDict

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.apps.suite import T_IN, T_OUT, build_knowledge_base
from repro.core import refresh_pipeline
from repro.core.refresh_config import RefreshConfig
from repro.core.refresh_mesh import RefreshMesh
from repro.core.refresh_pipeline import (_Crossings, _delta_pipeline,
                                         _prewarm_args, _ranked_args,
                                         refresh_ranks_delta)
from repro.core.scheduler import HermesScheduler

# constants a walked event dispatch needs: the two PrewarmTable arrays, K,
# and the (1, 1) arrival-arena, (1, 1, 1) posterior and int and float (1,)
# retrigger placeholders
EVENT_CONSTANTS = 7
EVENT_ROWS = 13          # the attained column and twelve row arrays


@pytest.fixture(scope="module")
def kb():
    return build_knowledge_base(n_trials=40, seed=0)


def _empty_cache(monkeypatch):
    monkeypatch.setattr(refresh_pipeline, "_RESIDENT", OrderedDict())


def _sched(kb, n_apps=8):
    s = HermesScheduler(kb, policy="gittins", t_in=T_IN, t_out=T_OUT,
                        mc_walkers=32, seed=11, prewarm=True,
                        refresh=RefreshConfig(mode="fused_delta",
                                              walker="pallas"))
    names = sorted(kb)
    for i in range(n_apps):
        s.on_arrival(f"a{i}", names[i % len(names)], now=0.0)
        s.on_progress(f"a{i}", 0.1 * i)
    s.priorities_arrays(0.0)          # the first tick walks every admission
    return s


def _event_h2d(s):
    """Uploads of one walked event dispatch."""
    before = s.refresh_stats["h2d"]
    s.on_requeue("a1", 1.0)
    s.priorities_arrays(1.0, ["a1"])
    return s.refresh_stats["h2d"] - before


def test_second_dispatch_uploads_no_constant(kb, monkeypatch):
    s = _sched(kb)
    _empty_cache(monkeypatch)
    assert _event_h2d(s) == EVENT_ROWS + EVENT_CONSTANTS
    assert _event_h2d(s) == EVENT_ROWS
    # a repack builds a new table object: its two arrays upload once
    token, tab = s._prewarm_tab
    s._prewarm_tab = (token, dataclasses.replace(tab))
    assert _event_h2d(s) == EVENT_ROWS + 2
    assert _event_h2d(s) == EVENT_ROWS


def test_constants_are_kept_by_identity_and_bounded(kb, monkeypatch):
    _empty_cache(monkeypatch)
    s = _sched(kb)
    packed, tab = s._packed[1], s._prewarm_table()
    io = _Crossings()
    first = _prewarm_args(packed, tab, io)
    assert io.h2d == 0                 # uploaded by the first tick
    assert _prewarm_args(packed, tab, io) is first
    assert io.zeros(1, 1) is io.zeros(1, 1) and io.scalar(s.K) is \
        io.scalar(s.K)
    np.testing.assert_array_equal(np.asarray(first[0]), tab.unit_class)
    np.testing.assert_array_equal(np.asarray(first[1]), tab.warmup)
    assert io.scalar(0.25).dtype == jnp.float32 and \
        not io.scalar(0.25).weak_type
    tables = [dataclasses.replace(tab)
              for _ in range(refresh_pipeline._RESIDENT_CAP + 4)]
    for t in tables:
        _prewarm_args(packed, t, io)
    assert len(refresh_pipeline._RESIDENT) == refresh_pipeline._RESIDENT_CAP
    # the newest entries hold their tables, so their ids stay theirs
    assert refresh_pipeline._RESIDENT[("prewarm", id(tables[-1]))][0] \
        is tables[-1]


def _bits(a):
    a = np.asarray(a)
    return a.dtype, a.shape, a.tobytes()


def _reference(s, qs, walked, retrigger):
    """``_delta_pipeline`` on ``qs`` as it stands, with every argument
    uploaded by ``jnp.asarray`` and every constant built afresh."""
    up = jnp.asarray
    packed, tab = s._packed[1], s._prewarm_table()
    gi, start, executed, attained, kid, rid, stretch, ovs, ovc = \
        qs.gather(walked)
    with_ov = qs.override_apps > 0
    if not with_ov:
        ovs = ovs[:, :, :1]
    D, ap = len(walked), len(gi)
    slot_idx = np.concatenate([walked, np.full(ap - D, qs.capacity)])
    if retrigger:
        delta_all = qs.attained - qs.a_att
        delta_all[walked] = 0.0
        rows = up(qs.graph_idx), up(delta_all), up(qs.stretch)
    else:
        z = jnp.zeros((1,), jnp.float32)
        rows = jnp.zeros((1,), jnp.int32), z, z
    rank_in_kernel, qsv, qic = _ranked_args(packed, s.walker, None,
                                            s.rank_in_kernel)
    return _delta_pipeline(
        packed.samples, packed.counts, packed.cum_trans,
        up(gi), up(start), up(executed), up(attained), up(kid), up(rid),
        s._base_key, up(np.uint32(int(s._seed) & 0xFFFFFFFF)),
        up(ovs), up(ovc), up(np.arange(ap) < D), up(stretch),
        up(slot_idx), qs.d_probs, qs.d_edges, up(qs.attained),
        qs.a_hist, qs.a_lo, qs.a_span, qs.a_reach, *rows,
        up(tab.unit_class), up(tab.warmup), jnp.float32(s.K),
        jnp.zeros((1, 1, 1), jnp.float32), qsv, qic,
        n_walkers=s.mc_walkers, max_steps=64, n_buckets=s.n_buckets,
        walker=s.walker, impl=None, with_overrides=with_ov,
        compact_after=s.compact_after, compact_shrink=s.compact_shrink,
        with_prewarm=True, with_retrigger=retrigger,
        with_triage=s._with_triage, rank_in_kernel=rank_in_kernel)


@pytest.mark.parametrize("path", ["event", "tick"])
def test_dispatch_is_bit_identical_to_uploaded_arguments(kb, path):
    s = _sched(kb)
    for i in range(8):
        s.on_progress(f"a{i}", 0.3)
    qs = s._qstate
    # three walked rows pad to four: the padding row is exercised too
    walked = np.asarray(sorted(qs.slot[f"a{i}"] for i in (1, 4, 6)),
                        np.int64)
    retrigger = path == "tick"
    ref = _reference(s, qs, walked, retrigger)
    tick = refresh_ranks_delta(
        s._packed[1], qs, s._base_key, s._seed, walked=walked,
        n_walkers=s.mc_walkers, n_buckets=s.n_buckets, walker=s.walker,
        compact_after=s.compact_after, compact_shrink=s.compact_shrink,
        prewarm_table=s._prewarm_table(), prewarm_k=s.K,
        retrigger=retrigger, with_triage=s._with_triage,
        rank_in_kernel=s.rank_in_kernel)
    assert _bits(tick.ranks) == _bits(ref[2])
    for name, i in (("d_probs", 0), ("d_edges", 1), ("a_hist", 7),
                    ("a_lo", 8), ("a_span", 9), ("a_reach", 10)):
        assert _bits(getattr(qs, name)) == _bits(ref[i]), name
    D = len(walked)
    trig, reach = np.asarray(ref[11]), np.asarray(ref[12])
    if retrigger:
        assert _bits(qs.trig) == _bits(trig)
        assert _bits(qs.reach) == _bits(reach)
    else:
        assert _bits(qs.trig[walked]) == _bits(trig[:D])
        assert _bits(qs.reach[walked]) == _bits(reach[:D])


def test_mesh_places_the_resident_placeholders(kb):
    n = 1 << (min(jax.device_count(), 8).bit_length() - 1)
    mesh = RefreshMesh(n)
    packed = _sched(kb, n_apps=2)._packed[1]
    io = _Crossings()
    uc, wt = _prewarm_args(packed, None, io)
    assert _prewarm_args(packed, None, io) == (uc, wt)
    placed = mesh.prewarm_constants(packed, None)
    assert mesh.prewarm_constants(packed, None) is placed
    want = NamedSharding(mesh.mesh, P())
    for got, src in zip(placed, (uc, wt)):
        assert got.sharding.is_equivalent_to(want, got.ndim)
        assert _bits(got) == _bits(src)
    assert uc.shape == (packed.samples.shape[0], packed.n_units, 1)
    assert (np.asarray(uc) == -1).all() and (np.asarray(wt) == 0).all()
