"""A refresh dispatch reads its results back in one overlapped transfer.

``refresh_ranks_delta`` starts every result's copy to the host at the
enqueue and reads them all together in ``.wait``.  Pinned here, for each
dispatch kind under ``gittins`` and under ``hermes_ddl`` (whose walked
dispatches also read the triage: seven reads):

* the returned ranks and spill, and every host mirror the dispatch writes
  (``qs.sup/opt/mean/trig/reach``), are the bits, dtype included, of the
  program's outputs read one by one with ``np.asarray``;
* a retrigger tick leaves ``qs.trig`` / ``qs.reach`` writable copies;
* every read's copy started at the enqueue: ``d2h_async == d2h``.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.apps.suite import T_IN, T_OUT, build_knowledge_base
from repro.core import refresh_pipeline
from repro.core import scheduler as scheduler_mod
from repro.core.refresh_config import RefreshConfig
from repro.core.scheduler import HermesScheduler

REFRESH = RefreshConfig(mode="fused_delta", walker="pallas")

# Reads per dispatch kind: a walk reads ranks, spill, trigger and reach,
# and sup, opt and mean with triage; a tick with nothing to walk ranks,
# trigger and reach; an event with nothing to walk ranks alone.
READS = {
    "gittins": {"event_walk": 4, "event_rank": 1,
                "tick_walk": 4, "tick_rank": 3},
    "hermes_ddl": {"event_walk": 7, "event_rank": 1,
                   "tick_walk": 7, "tick_rank": 3},
}
CASES = [(p, k) for p in sorted(READS) for k in sorted(READS[p])]

# the outputs of each program the dispatch calls, by position
OUTPUTS = {
    "_delta_pipeline": ("d_probs", "d_edges", "ranks", "spill", "sup",
                        "opt", "mean", "a_hist", "a_lo", "a_span",
                        "a_reach", "trigger", "reach"),
    "_rank_retrigger_pipeline": ("ranks", "trigger", "reach"),
    "gittins_rank_hist": ("ranks",),
}


@pytest.fixture(scope="module")
def kb():
    return build_knowledge_base(n_trials=40, seed=0)


def _sched(kb, policy, n_apps=8):
    s = HermesScheduler(kb, policy=policy, t_in=T_IN, t_out=T_OUT,
                        mc_walkers=32, seed=11, prewarm=True,
                        refresh=REFRESH)
    names = sorted(kb)
    for i in range(n_apps):
        s.on_arrival(f"a{i}", names[i % len(names)], now=0.0,
                     deadline=60.0 + 20.0 * i)
        s.on_progress(f"a{i}", 0.1 * i)
    s.priorities_arrays(0.0)          # the first tick walks every admission
    return s


def _follow(monkeypatch):
    """Record, for each dispatch, its program's outputs each read alone
    from a device copy of its own, and the dispatch's arguments, store and
    returned tick."""
    log = []
    for name, names in OUTPUTS.items():
        def call(*a, _orig=getattr(refresh_pipeline, name), _names=names,
                 **kw):
            got = _orig(*a, **kw)
            outs = got if isinstance(got, tuple) else (got,)
            log.append({"serial": {n: np.asarray(jnp.array(v, copy=True))
                                   for n, v in zip(_names, outs)
                                   if v is not None}})
            return got
        monkeypatch.setattr(refresh_pipeline, name, call)

    orig_delta = scheduler_mod.refresh_ranks_delta

    def refresh_ranks_delta(packed, qs, *a, **kw):
        tick = orig_delta(packed, qs, *a, **kw)
        log[-1].update(qs=qs, tick=tick, kw=kw)
        return tick
    monkeypatch.setattr(scheduler_mod, "refresh_ranks_delta",
                        refresh_ranks_delta)
    return log


def _dispatch(s, kind):
    if kind.endswith("walk"):
        s.on_requeue("a1", 1.0)        # one dirty slot to walk
    s.priorities_arrays(1.0, ["a1"] if kind.startswith("event") else None)


def _same_bits(got, want):
    got = np.asarray(got)
    assert got.dtype == want.dtype
    assert got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("policy,kind", CASES)
def test_one_read_gives_the_serial_reads_bits(kb, monkeypatch, policy,
                                              kind):
    s = _sched(kb, policy)
    log = _follow(monkeypatch)
    for _ in range(2):                 # the compiling dispatch and the next
        del log[:]
        _dispatch(s, kind)
        (rec,) = log
        serial, qs, tick, kw = rec["serial"], rec["qs"], rec["tick"], \
            rec["kw"]
        walked = np.asarray(tick.walked)
        D = len(walked)
        assert (D > 0) == kind.endswith("walk")
        assert kw["retrigger"] == kind.startswith("tick")
        assert tick.d2h == READS[policy][kind]
        assert tick.d2h_async == tick.d2h

        _same_bits(tick.ranks, serial["ranks"])
        if D:
            assert type(tick.spill) is int
            assert tick.spill == int(serial["spill"])
        if kw["with_triage"] and D:
            for k in ("sup", "opt", "mean"):
                _same_bits(getattr(qs, k)[walked], serial[k][:D])
        if "trigger" not in serial:
            continue
        if kw["retrigger"]:
            for k, mirror in (("trigger", qs.trig), ("reach", qs.reach)):
                _same_bits(mirror, serial[k])
                assert mirror.flags.writeable
        else:
            _same_bits(qs.trig[walked], serial["trigger"][:D])
            _same_bits(qs.reach[walked], serial["reach"][:D])


def test_a_host_result_is_read_without_a_copy_started():
    """A result that is already on the host (a plant, a CPU path) passes
    through ``start`` untouched and is counted as read, not as started."""
    io = refresh_pipeline._Crossings()
    out = {"ranks": jnp.arange(4.0), "spill": np.int32(3)}
    io.start(out)
    got = io.get(out)
    assert (io.d2h, io.d2h_async) == (2, 1)
    _same_bits(got["ranks"], np.arange(4.0, dtype=np.float32))
    assert got["spill"] == 3
