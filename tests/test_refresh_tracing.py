"""The refresh dispatch's host spans and crossing counters.

Every host->device upload of a single-arena delta dispatch goes through the
counting helpers (``put``, or ``make`` for a constant built on the device),
every device->host read through ``get``; the scheduler sums them into
``refresh_stats``.  Pinned here:

* no upload bypasses the helpers: whole dispatches run under JAX's
  host->device transfer guard, which refuses every implicit transfer but
  the device-built constants', and a logging guard finds one of those per
  ``make``;
* the counts per dispatch, derived from the code path by path;
* a simulator run under the profiler writes the program's spans
  (``hermes.<path>.{prepare,wait,consume}``, ``hermes.rekey``), with one
  ``wait`` span per counted dispatch.
"""
import collections
import glob

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
# mask, stretch, the scatter slots, the (1, 1) arrival-arena and
# (1, 1, 1) posterior placeholders, the two PrewarmTable constants and K:
# 18; then either the three arena-wide retrigger rows (full tick) or the
# int and float retrigger placeholders (event).  Reads: ranks, spill,
# trigger, reach.  A tick with nothing to walk uploads attained, the two
# constants, the three retrigger rows and K, and reads ranks, trigger and
# reach; an event with nothing to walk uploads attained and reads ranks.
CROSSINGS = {
    "event_walk": ("event_dispatches", 20, 4),
    "event_rank": ("event_dispatches", 1, 1),
    "tick_walk": ("tick_dispatches", 21, 4),
    "tick_rank": ("tick_dispatches", 7, 3),
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
    for _ in range(2):                 # the second dispatch is compiled
        key, h2d, d2h = CROSSINGS[kind]
        got = _dispatch(s, kind)
        want = {"event_dispatches": 0, "tick_dispatches": 0,
                "h2d": h2d, "d2h": d2h}
        want[key] = 1
        assert got == want


def test_every_upload_is_counted(kb, monkeypatch, capfd):
    """With implicit host->device transfers refused, whole dispatches of
    each kind still run once the device-built constants (``make``) are let
    through: every other upload goes through ``put``.  Under a logging
    guard the same dispatches make exactly one implicit transfer per
    ``make``, its fill value, so each is counted once."""
    s = _sched(kb)
    kinds = sorted(CROSSINGS)
    for kind in kinds:                 # compile each shape first
        _dispatch(s, kind)
    made = [0]
    make = _Crossings.make

    def counted(allow):
        def fn(self, *a):
            made[0] += 1
            if not allow:
                return make(self, *a)
            with jax.transfer_guard_host_to_device("allow"):
                return make(self, *a)
        return fn

    monkeypatch.setattr(_Crossings, "make", counted(allow=True))
    with jax.transfer_guard_host_to_device("disallow"):
        for kind in kinds:
            _dispatch(s, kind)
    assert made[0] > 0
    with pytest.raises(Exception, match="host-to-device"):
        with jax.transfer_guard_host_to_device("disallow"):
            jax.numpy.zeros(1) + 1     # the guard does refuse an implicit one

    def logged(run):
        capfd.readouterr()
        with jax.transfer_guard_host_to_device("log"):
            run()
        return capfd.readouterr().err.count("host-to-device transfer")

    assert logged(lambda: jax.numpy.zeros(1)) == 1
    monkeypatch.setattr(_Crossings, "make", counted(allow=False))
    made[0] = 0
    n = logged(lambda: [_dispatch(s, kind) for kind in kinds])
    assert n == made[0] > 0


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
        {"hermes.rekey"}
