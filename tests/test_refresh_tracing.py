"""The refresh dispatch's host spans and crossing counters.

Every per-dispatch host->device upload of a single-arena delta dispatch
goes through the counting helper ``put``, which hands the host array to
the jit; the constants (prewarm tables, K, placeholders) stay resident
on the device after one counted upload; every device->host read starts
its copy at the enqueue (``start``) and is read back in ``get``.  The
scheduler sums the counts into ``refresh_stats``.
Pinned here:

* no upload bypasses the helpers and no constant crosses again: under a
  logging transfer guard a compiled dispatch makes as many transfers as
  it counts, less the arguments its program does not read; with ``put``
  made an explicit upload it logs exactly as many, and whole dispatches
  run under JAX's guard that refuses every implicit transfer;
* the counts per dispatch, derived from the code path by path;
* a simulator run under the profiler writes the program's spans
  (``hermes.<path>.{prepare,wait,consume}``, ``hermes.key``,
  ``hermes.rekey``), with one ``wait`` span per counted dispatch.
"""
import collections
import glob

import numpy as np
import pytest

import jax

from repro.apps.suite import T_IN, T_OUT, build_knowledge_base
from repro.apps.workload import make_open_workload
from repro.core.refresh_config import RefreshConfig
from repro.core.refresh_pipeline import _Crossings
from repro.core.scheduler import HermesScheduler
from repro.runtime.tracing import PREFIX
from repro.serving.simulator import ClusterSim, SimConfig

REFRESH = RefreshConfig(mode="fused_delta", walker="pallas")

# Uploads of a walked dispatch (prewarm on, Gittins, no posterior): the
# arena's attained column, six row columns (graph, start unit, executed,
# attained, key id, refresh id), the seed, two override columns, the valid
# mask, stretch and the scatter slots: 13; a full tick adds the three
# arena-wide retrigger rows.  The two PrewarmTable arrays, K and the
# placeholders ((1, 1) arrival arena, (1, 1, 1) posterior, int and float
# (1,) retrigger rows) are resident and cross only on the dispatch that
# first needs them.  Reads: ranks, spill, trigger, reach.  A tick with
# nothing to walk uploads attained and the three retrigger rows, and reads
# ranks, trigger and reach; an event with nothing to walk uploads attained
# and reads ranks.
CROSSINGS = {
    "event_walk": ("event_dispatches", 13, 4),
    "event_rank": ("event_dispatches", 1, 1),
    "tick_walk": ("tick_dispatches", 16, 4),
    "tick_rank": ("tick_dispatches", 4, 3),
}

@pytest.fixture(scope="module")
def kb():
    return build_knowledge_base(n_trials=40, seed=0)


def _sched(kb, n_apps=8):
    s = HermesScheduler(kb, policy="gittins", t_in=T_IN, t_out=T_OUT,
                        mc_walkers=32, seed=11, prewarm=True,
                        refresh=REFRESH)
    names = sorted(kb)
    for i in range(n_apps):
        s.on_arrival(f"a{i}", names[i % len(names)], now=0.0)
        s.on_progress(f"a{i}", 0.1 * i)
    s.priorities_arrays(0.0)          # the first tick walks every admission
    return s


def _dispatch(s, kind):
    """One dispatch of ``kind``; returns the change of ``refresh_stats``."""
    before = dict(s.refresh_stats)
    if kind.endswith("walk"):
        s.on_requeue("a1", 1.0)        # one dirty slot to walk
    ids, ranks = s.priorities_arrays(1.0, ["a1"] if kind.startswith("event")
                                     else None)
    assert len(ids) == len(ranks) >= 1
    return {k: s.refresh_stats[k] - before[k] for k in before}


@pytest.mark.parametrize("kind", sorted(CROSSINGS))
def test_dispatch_counts_its_crossings(kb, kind):
    s = _sched(kb)
    key, h2d, d2h = CROSSINGS[kind]
    # every read's host copy starts at the enqueue
    want = {"event_dispatches": 0, "tick_dispatches": 0,
            "h2d": h2d, "d2h": d2h, "d2h_async": d2h}
    want[key] = 1
    for i in range(3):
        got = _dispatch(s, kind)
        if i == 0:
            # the first dispatch compiles, and uploads any constant it is
            # the first to need
            assert got["h2d"] >= h2d
            got["h2d"] = h2d
        assert got == want


def _transfers(capfd, mode, run):
    """Host->device transfers ``run`` makes, as logged under ``mode``."""
    capfd.readouterr()
    with jax.transfer_guard_host_to_device(mode):
        run()
    return capfd.readouterr().err.count("host-to-device transfer")


# Counted uploads that the jit drops before any transfer, because the
# compiled program does not read them: the two override columns when no
# override is live, and on a full tick also the walked rows' stretch (the
# retrigger reads the arena-wide stretch row).
UNREAD = {"event_walk": 2, "event_rank": 0, "tick_walk": 3, "tick_rank": 0}


def test_every_upload_is_counted(kb, monkeypatch, capfd):
    """Once each shape is compiled, a dispatch of each kind logs as many
    host->device transfers as it counts in ``h2d``, less the arguments its
    program does not read, so no constant crosses again.  With ``put``
    made an explicit upload it logs exactly ``h2d``, implicit or explicit,
    and whole dispatches run under a guard that refuses every implicit
    transfer, so none bypasses ``put``."""
    s = _sched(kb)
    for kind in sorted(CROSSINGS):     # compile each shape first
        _dispatch(s, kind)
    assert _transfers(capfd, "log_explicit",
                      lambda: jax.device_put(np.zeros(1))) == 1
    assert _transfers(capfd, "log",
                      lambda: jax.jit(lambda x: x)(np.zeros(1))) == 1

    def logged(mode, kind):
        got = {}
        n = _transfers(capfd, mode, lambda: got.update(_dispatch(s, kind)))
        assert got["h2d"] == CROSSINGS[kind][1], kind
        return n

    for kind in sorted(CROSSINGS):
        assert logged("log", kind) == CROSSINGS[kind][1] - UNREAD[kind]
    put = _Crossings.put
    monkeypatch.setattr(_Crossings, "put",
                        lambda self, x: jax.device_put(put(self, x)))
    for kind in sorted(CROSSINGS):
        assert logged("log_explicit", kind) == CROSSINGS[kind][1], kind
    with pytest.raises(Exception, match="host-to-device"):
        with jax.transfer_guard_host_to_device("disallow"):
            jax.jit(lambda x: x)(np.zeros(1))   # the guard does refuse one
    with jax.transfer_guard_host_to_device("disallow"):
        for kind in sorted(CROSSINGS):
            _dispatch(s, kind)


def test_sim_run_writes_the_spans(kb, tmp_path):
    insts = make_open_workload(30.0, t_in=T_IN, t_out=T_OUT, rate_per_s=0.5,
                               process="gamma", cv=2.5, seed=5, max_apps=12)
    sim = ClusterSim(kb, SimConfig(policy="gittins", mc_walkers=32, seed=3,
                                   n_llm_slots=4, n_docker_slots=6,
                                   n_dnn_slots=2, refresh=REFRESH))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        sim.run(insts)
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(sorted(files)[-1])
    n = collections.Counter(
        e.name for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith(PREFIX))
    stats = sim.sched.refresh_stats
    for path in ("event", "tick"):
        dispatches = stats[f"{path}_dispatches"]
        assert dispatches > 0
        assert n[f"hermes.{path}.wait"] == dispatches
        # the scheduler's and the pipeline's part of each phase
        assert n[f"hermes.{path}.prepare"] == 2 * dispatches
        assert n[f"hermes.{path}.consume"] == 2 * dispatches
    assert n["hermes.rekey"] >= stats["tick_dispatches"]
    assert set(n) == {f"hermes.{p}.{ph}" for p in ("event", "tick")
                      for ph in ("prepare", "wait", "consume")} | \
        {"hermes.rekey", "hermes.key"}
