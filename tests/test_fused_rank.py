"""One-pass VMEM-resident refresh: ``pdgraph_walk_ranked`` vs the oracle.

The acceptance contract (ISSUE 9): the fused kernel's in-kernel demand
histogram rows, Gittins ranks, and arrival sufficient statistics are
bit-identical to composing ``pdgraph_walk`` + ``to_histogram_rows_jnp`` +
``gittins_rank_core`` + ``_arrival_hists`` — across attained-service
offsets, pad rows, multi-stage compaction, posterior-blended tables, and
the quantized CPU twin — in interpret mode, and through every pipeline
entry point (``rank_in_kernel`` on vs off must not change a bit).

Shard counts above the visible device count skip; CI's multi-device leg
runs the mesh matrix under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.apps.suite import T_IN, T_OUT, build_knowledge_base
from repro.core.gittins import gittins_rank_core, to_histogram_rows_jnp
from repro.core.pdgraph import pack_graphs
from repro.core.refresh_config import RefreshConfig
from repro.core.refresh_pipeline import _arrival_hists
from repro.core.scheduler import HermesScheduler
from repro.kernels.pdgraph_walk import ops
from repro.kernels.pdgraph_walk.ops import (pdgraph_walk, pdgraph_walk_ranked,
                                            walk_schedule, walker_streams)
from repro.kernels.pdgraph_walk.quant import quant_tables

W, STEPS, NB = 32, 24, 10


def _needs(n):
    return pytest.mark.skipif(
        jax.device_count() < n,
        reason=f"needs {n} devices (XLA_FLAGS="
               f"--xla_force_host_platform_device_count={n})")


SHARD_PARAMS = [pytest.param(n, marks=_needs(n)) for n in (1, 2, 8)]


@pytest.fixture(scope="module")
def kb():
    return build_knowledge_base(n_trials=40, seed=3)


@pytest.fixture(scope="module")
def packed(kb):
    return pack_graphs(kb, T_IN, T_OUT)


def _queue(packed, n, seed=0, attained="rand"):
    rng = np.random.default_rng(seed)
    gi = rng.integers(0, packed.samples.shape[0], n).astype(np.int32)
    start = np.asarray(packed.entry)[gi].astype(np.int32)
    ex = rng.uniform(0.0, 0.5, n).astype(np.float32)
    att = {"zero": np.zeros(n, np.float32),
           "rand": rng.uniform(0.0, 3.0, n).astype(np.float32),
           "large": np.full(n, 37.5, np.float32)}[attained]
    streams = walker_streams(7, np.arange(n), np.zeros(n, np.int32))
    return (jnp.asarray(gi), jnp.asarray(start), jnp.asarray(ex),
            jnp.asarray(att), streams)


def _oracle(packed, gi, start, ex, att, streams, valid=None, po=None,
            arrivals=False):
    """The three-dispatch composition the fused program must reproduce."""
    po_kw = {} if po is None else dict(po_cum=po[0], po_scale=po[1])
    out = pdgraph_walk(packed.samples, packed.counts, packed.cum_trans,
                       gi, start, ex, streams, valid=valid, impl="ref",
                       compact_schedule=((4, 2),), n_walkers=W,
                       max_steps=STEPS, track_arrivals=arrivals, **po_kw)
    if arrivals:
        rem, arr, _ = out
    else:
        (rem, _), arr = out, None
    total = att[:, None] + jnp.maximum(rem, 0.0)
    probs, edges = to_histogram_rows_jnp(total, NB)
    res = dict(total=total, probs=probs, edges=edges,
               ranks=gittins_rank_core(probs, edges, att))
    if arrivals:
        h, lo, sp, rc = _arrival_hists(arr, NB)
        res.update(a_hist=h, a_lo=lo, a_span=sp, a_reach=rc)
    return res


def _ranked(packed, gi, start, ex, att, streams, **kw):
    return pdgraph_walk_ranked(packed.samples, packed.counts,
                               packed.cum_trans, gi, start, ex, streams,
                               att, n_walkers=W, max_steps=STEPS, **kw)


def _assert_keys(r, o, keys, tag=""):
    for k in keys:
        np.testing.assert_array_equal(np.asarray(r[k]), np.asarray(o[k]),
                                      err_msg=f"{tag}{k}")


# ------------------------------------------------- kernel vs oracle (bitwise)

@pytest.mark.parametrize("attained", ["zero", "rand", "large"])
def test_kernel_matches_oracle_across_attained_offsets(packed, attained):
    """The in-kernel histogram + rank epilogue is bit-identical to the
    composed reduction at every attained-service offset (attained shifts
    every bucket edge, so bucketing AND the rank sweep must agree)."""
    gi, start, ex, att, streams = _queue(packed, 8, attained=attained)
    r = _ranked(packed, gi, start, ex, att, streams, impl="pallas",
                interpret=True, with_total=True)
    o = _oracle(packed, gi, start, ex, att, streams)
    _assert_keys(r, o, ("probs", "edges", "ranks", "total"))
    assert int(r["spill"]) == 0


def test_cpu_twin_quant_multistage_matches_oracle(packed):
    """The CPU twin — lossless 16-bit quantized step + the lane-gated
    multi-stage compaction schedule — returns the oracle's bits.  32 rows
    so the (4, 2) knobs expand to a live two-stage schedule."""
    gi, start, ex, att, streams = _queue(packed, 32, seed=1)
    assert walk_schedule(6, 2, 32 * W) == ((6, 2), (12, 8))
    qt = quant_tables(packed.samples, packed.counts, packed.cum_trans)
    r = _ranked(packed, gi, start, ex, att, streams, impl="ref",
                with_total=True, quant=qt, compact_after=6, compact_shrink=2)
    assert int(r["spill"]) == 0      # spill-free: identity must be exact
    o = _oracle(packed, gi, start, ex, att, streams)
    _assert_keys(r, o, ("probs", "edges", "ranks", "total"))


def test_kernel_pad_rows_do_not_leak(packed):
    """valid=False pad rows start absorbed; real rows' histogram rows and
    ranks must match a walk of the same rows without the padding mask."""
    gi, start, ex, att, streams = _queue(packed, 8, seed=2)
    valid = jnp.asarray(np.array([1, 1, 0, 1, 1, 0, 1, 1], bool))
    r = _ranked(packed, gi, start, ex, att, streams, valid=valid,
                impl="pallas", interpret=True)
    o = _oracle(packed, gi, start, ex, att, streams, valid=valid)
    vm = np.asarray(valid)
    for k in ("probs", "edges", "ranks"):
        np.testing.assert_array_equal(np.asarray(r[k])[vm],
                                      np.asarray(o[k])[vm], err_msg=k)


def _po_tables(packed, n, seed=5):
    rng = np.random.default_rng(seed)
    U = packed.n_units
    cum = np.sort(rng.uniform(0, 1, (n, U, U + 1)).astype(np.float32),
                  axis=-1)
    cum[..., -1] = 2.0
    scale = rng.uniform(0.5, 1.5, (n, U)).astype(np.float32)
    return jnp.asarray(cum), jnp.asarray(scale)


@pytest.mark.parametrize("arrivals", [False, True])
def test_kernel_posterior_tables_no_longer_fall_back(packed, arrivals):
    """Posterior-blended tables (and arrivals tracking) run IN the fused
    kernel now — the closed twin-fallback gaps — and still match the
    composed reference bit-for-bit, jointly and separately."""
    gi, start, ex, att, streams = _queue(packed, 8, seed=3)
    po = _po_tables(packed, 8)
    keys = ["probs", "edges", "ranks"]
    if arrivals:
        keys += ["a_hist", "a_lo", "a_span", "a_reach"]
    r = _ranked(packed, gi, start, ex, att, streams, impl="pallas",
                interpret=True, po_cum=po[0], po_scale=po[1],
                track_arrivals=arrivals)
    o = _oracle(packed, gi, start, ex, att, streams, po=po,
                arrivals=arrivals)
    _assert_keys(r, o, keys, "pallas.")
    # the quantized twin blends the same posterior rows (mixed step: quant
    # service gather + posterior transition compare)
    qt = quant_tables(packed.samples, packed.counts, packed.cum_trans)
    rq = _ranked(packed, gi, start, ex, att, streams, impl="ref", quant=qt,
                 po_cum=po[0], po_scale=po[1], track_arrivals=arrivals)
    _assert_keys(rq, o, keys, "quant.")


def test_kernel_arrival_stats_match_oracle(packed):
    gi, start, ex, att, streams = _queue(packed, 8, seed=4)
    r = _ranked(packed, gi, start, ex, att, streams, impl="pallas",
                interpret=True, track_arrivals=True)
    o = _oracle(packed, gi, start, ex, att, streams, arrivals=True)
    _assert_keys(r, o, ("probs", "edges", "ranks",
                        "a_hist", "a_lo", "a_span", "a_reach"))


def test_walk_schedule_gates():
    """Off stays off; tuned knobs extend one tail stage; the default knobs
    open the measured three-stage schedule only at >= 16k lanes."""
    assert walk_schedule(16, 1, 1 << 20) == ((16, 1),)
    assert walk_schedule(0, 4, 1 << 20) == ((0, 4),)
    assert walk_schedule(8, 2, 1 << 20) == ((8, 2), (16, 8))
    assert walk_schedule(16, 4, 1 << 20) == ((12, 4), (28, 16), (44, 64))
    assert walk_schedule(16, 4, 1024) == ((16, 4),)


# ------------------------------------------------- the silent-fallback trap

def test_dispatch_is_recorded_and_fallback_warns(packed):
    """A requested kernel path must either run the kernel or warn ONCE per
    reason — never silently take the twin."""
    gi, start, ex, att, streams = _queue(packed, 4, seed=6)
    _ranked(packed, gi, start, ex, att, streams, impl="pallas",
            interpret=True)
    assert ops.LAST_DISPATCH == "pallas"
    _ranked(packed, gi, start, ex, att, streams, impl="ref")
    assert ops.LAST_DISPATCH == "ref"
    # auto dispatch off-TPU is the twin BY CHOICE (requested=None): no warn
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        pdgraph_walk(packed.samples, packed.counts, packed.cum_trans,
                     gi, start, ex, streams, n_walkers=W, max_steps=STEPS)
    assert ops.LAST_DISPATCH == ("pallas" if jax.default_backend() == "tpu"
                                 else "ref")
    # a forced fallback warns, once, naming the reason
    reason = "test-reason-fused-rank"
    ops._FALLBACK_WARNED.discard(reason)
    try:
        with pytest.warns(RuntimeWarning, match=reason):
            ops._note_dispatch("pallas", "ref", reason)
        with _w.catch_warnings():
            _w.simplefilter("error")
            ops._note_dispatch("pallas", "ref", reason)   # one-time only
    finally:
        ops._FALLBACK_WARNED.discard(reason)


# ------------------------------------------------- pipeline-level identity

MC = 32


def _filled(kb, rik=None, mode="fused_delta", mesh=None, lane=None,
            policy="gittins", prewarm=False, posterior=None, n_apps=24):
    rc = RefreshConfig(mode=mode, walker="pallas", rank_in_kernel=rik,
                      mesh_shards=mesh, lane_balance=lane)
    s = HermesScheduler(kb, policy=policy, t_in=T_IN, t_out=T_OUT,
                        mc_walkers=MC, seed=11, refresh=rc, prewarm=prewarm,
                        posterior=posterior)
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


def _check(tag, a, b):
    ia, va = _vals(a)
    ib, vb = _vals(b)
    assert ia == ib, tag
    np.testing.assert_array_equal(va, vb, err_msg=tag)


def test_rank_in_kernel_config_resolution():
    assert RefreshConfig(walker="pallas").rank_in_kernel is True
    assert RefreshConfig(walker="threefry").rank_in_kernel is False
    assert RefreshConfig(walker="pallas",
                         rank_in_kernel=False).rank_in_kernel is False
    with pytest.raises(ValueError, match="rank_in_kernel"):
        RefreshConfig(walker="threefry", rank_in_kernel=True)
    with pytest.raises(ValueError, match="lane_balance"):
        RefreshConfig(lane_balance=0.25)            # needs mesh_shards
    with pytest.raises(ValueError, match="lane_balance"):
        RefreshConfig(mesh_shards=2, lane_balance=-1.0)


@pytest.mark.parametrize("mesh", [None, 1], ids=["fused_delta", "mesh1"])
def test_pipeline_rank_in_kernel_bit_identity(kb, mesh):
    """The one-pass program and the legacy walk -> histogram -> rank
    composition return identical priorities across ticks with churn, on
    the single arena and on a one-shard mesh."""
    a = _filled(kb, rik=True, mesh=mesh)
    b = _filled(kb, rik=False, mesh=mesh)
    _check(f"mesh={mesh} tick1", a.priorities(10.0), b.priorities(10.0))
    for s in (a, b):
        for i in range(0, 24, 3):
            s.on_progress(f"a{i:03d}", 0.7)
        s.on_unit_start("a004", s.apps["a004"].current_unit, 11.0)
    _check(f"mesh={mesh} tick2", a.priorities(12.0), b.priorities(12.0))


def test_pipeline_rank_in_kernel_with_posterior(kb):
    from repro.core.posterior import PosteriorConfig

    def run(rik):
        s = _filled(kb, rik=rik, posterior=PosteriorConfig(), n_apps=0)
        for i in range(8):
            s.on_arrival(f"b{i}", "CG", now=float(i))
            s.on_progress(f"b{i}", 0.1 * i)
        s.priorities(8.0)
        for i in range(6):
            s.on_unit_finish(f"b{i}", "plan",
                             {"in": 500, "out": 280, "par": 1}, 9.0,
                             "generate")
        return s.priorities(10.0)

    _check("delta+posterior", run(True), run(False))


def test_pipeline_rank_in_kernel_with_prewarm(kb):
    a = _filled(kb, rik=True, policy="hermes_ddl", prewarm=True)
    b = _filled(kb, rik=False, policy="hermes_ddl", prewarm=True)
    _check("prewarm ranks", a.priorities(10.0), b.priorities(10.0))
    pa, pb = a.take_prewarm_plan(), b.take_prewarm_plan()
    assert sorted(zip(pa.app_ids, pa.resource_keys, pa.fire_at,
                      pa.p_reach)) == \
        sorted(zip(pb.app_ids, pb.resource_keys, pb.fire_at, pb.p_reach))


# ------------------------------------------------- mesh + lane balancing

def _skewed_ticks(kb, mesh, lane, rik=None, policy="gittins",
                  prewarm=False, spy=None):
    s = _filled(kb, rik=rik, mesh=mesh, lane=lane, policy=policy,
                prewarm=prewarm)
    if spy is not None:
        s_ticks = []
        import repro.core.scheduler as sched_mod
        orig = sched_mod.refresh_ranks_mesh

        def wrapper(*a, **kw):
            tick = orig(*a, **kw)
            s_ticks.append(bool(tick.balanced))
            return tick

        spy(sched_mod, wrapper, s_ticks)
    r1 = s.priorities(10.0)
    # unit transitions only on slots with residue 0 mod 4: walk-dirty set
    # skewed for 2 AND 8 shards, fraction 0.25 (under delta_full_threshold)
    for i in range(0, 24, 4):
        aid = f"a{i:03d}"
        s.on_unit_start(aid, s.apps[aid].current_unit, 11.0)
    r2 = s.priorities(12.0)
    plan = s.take_prewarm_plan() if prewarm else None
    return r1, r2, plan


@pytest.mark.parametrize("n_shards", SHARD_PARAMS)
@pytest.mark.parametrize("rik", [None, False])
def test_mesh_rank_in_kernel_bit_identical(kb, n_shards, rik):
    """Mesh ticks with the one-pass program (and without) match the
    single-arena delta path bitwise, shard count notwithstanding."""
    m1, m2, _ = _skewed_ticks(kb, n_shards, None, rik=rik)
    d1, d2, _ = _skewed_ticks(kb, None, None, rik=rik)
    _check(f"n={n_shards} tick1", m1, d1)
    _check(f"n={n_shards} tick2", m2, d2)


@pytest.mark.parametrize("n_shards", [pytest.param(n, marks=_needs(n))
                                      for n in (2, 8)])
@pytest.mark.parametrize("policy,prewarm", [("gittins", False),
                                            ("hermes_ddl", True)])
def test_mesh_lane_balance_bit_identical(kb, monkeypatch, n_shards, policy,
                                         prewarm):
    """lane_balance=0.0 redistributes the skewed walk-dirty set round-robin
    (the balanced all-gather tick MUST trigger) and still returns the
    unbalanced tick's — and the single arena's — exact bits, prewarm plan
    included."""
    def spy(mod, wrapper, ticks):
        monkeypatch.setattr(mod, "refresh_ranks_mesh", wrapper)
        spy.ticks = ticks

    b1, b2, bp = _skewed_ticks(kb, n_shards, 0.0, policy=policy,
                               prewarm=prewarm, spy=spy)
    assert any(spy.ticks), "balanced tick never triggered"
    monkeypatch.undo()
    u1, u2, up = _skewed_ticks(kb, n_shards, None, policy=policy,
                               prewarm=prewarm)
    d1, d2, dp = _skewed_ticks(kb, None, None, policy=policy,
                               prewarm=prewarm)
    _check("tick1 bal-vs-unbal", b1, u1)
    _check("tick2 bal-vs-unbal", b2, u2)
    _check("tick2 bal-vs-delta", b2, d2)
    if prewarm:
        key = lambda p: sorted(zip(p.app_ids, p.resource_keys,  # noqa: E731
                                   p.fire_at, p.p_reach))
        assert key(bp) == key(up) == key(dp)
