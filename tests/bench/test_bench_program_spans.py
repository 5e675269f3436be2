"""The program's own trace marks seen by the trace reduction: its host
spans (``hermes.*``) nested inside the harness's leave every device
reading as it was, label the idle gaps they cover by their full name, and
are counted and summed by name for the phase readers; its counters are
read as the window moved them; and the walk kernels, named on the device
trace, are still the kernel that ``walk_device_us_per_row`` reads."""
import pytest

from bench import trace_reduce as tr
from bench.harness import metric_reader

PHASES = [f"{p}_{ph}_ms" for p in ("event", "tick")
          for ph in ("prepare", "wait", "consume")]


def _trace(program_spans):
    # window 1,000 ns; device busy 0-100 and 600-700; an event refresh over
    # 100-400 holds the program's prepare, wait and consume spans
    device = {"/device:TPU:0": [("k", 0, 100), ("fusion.2", 600, 100)]}
    host = [("bench.window", 0, 1000), ("bench.event_refresh", 100, 300)]
    if program_spans:
        host += [("hermes.event.prepare", 100, 150),
                 ("hermes.event.wait", 250, 100),
                 ("hermes.event.consume", 350, 50),
                 ("hermes.rekey", 400, 100)]
    return device, host


def test_program_spans_leave_the_readings_as_they_were():
    plain = tr.reduce_events(*_trace(False))
    spanned = tr.reduce_events(*_trace(True))
    for k in ("window_s", "busy_s", "n_ops", "op_s"):
        assert spanned[k] == plain[k]
    assert spanned["breakdown"]["device_ops"] == \
        plain["breakdown"]["device_ops"]
    assert dict(plain["breakdown"]["idle_gaps"]) == pytest.approx(
        {"event_refresh": 500e-9, "engine": 300e-9})
    # gaps 100-600 (midpoint 350: the consume span starts there) and
    # 700-1,000 (no span); a program span keeps its full name
    assert dict(spanned["breakdown"]["idle_gaps"]) == pytest.approx(
        {"hermes.event.consume": 500e-9, "engine": 300e-9})
    assert plain["spans"] == {}
    rec = {"trace": spanned, "rows_walked": 1}
    assert metric_reader("device_idle_pct")(rec) == pytest.approx(80.0)


def test_phase_readers_count_spans_inside_the_window():
    device = {"/device:TPU:0": [("k", 0, 10)]}
    ms = 1e6                                    # ns in a millisecond
    host = [("bench.window", 10 * ms, 100 * ms),
            # two event dispatches in the window, one before it, one
            # across its close
            ("hermes.event.prepare", 0, 2 * ms),
            ("hermes.event.wait", 2 * ms, 1 * ms),
            ("hermes.event.prepare", 20 * ms, 2 * ms),
            ("hermes.event.prepare", 22 * ms, 1 * ms),
            ("hermes.event.wait", 23 * ms, 3 * ms),
            ("hermes.event.consume", 26 * ms, 0.5 * ms),
            ("hermes.event.prepare", 40 * ms, 3 * ms),
            ("hermes.event.wait", 43 * ms, 1 * ms),
            ("hermes.event.consume", 44 * ms, 0.5 * ms),
            ("hermes.event.prepare", 109 * ms, 0.5 * ms),
            ("hermes.event.wait", 109.5 * ms, 1 * ms)]
    out = tr.reduce_events(device, host)
    assert out["spans"]["hermes.event.wait"]["n"] == 2
    assert out["spans"]["hermes.event.prepare"]["n"] == 4
    assert out["spans"]["hermes.event.prepare"]["s"] == pytest.approx(
        6.5e-3)
    rec = {"trace": out}
    got = {name: metric_reader(name)(rec) for name in PHASES}
    assert got["event_prepare_ms"] == pytest.approx(3.25)
    assert got["event_wait_ms"] == pytest.approx(2.0)
    assert got["event_consume_ms"] == pytest.approx(0.5)
    # the tick path had no dispatch: nothing to read
    assert got["tick_prepare_ms"] is got["tick_wait_ms"] is \
        got["tick_consume_ms"] is None
    for name in PHASES:                           # an untraced run
        assert metric_reader(name)({"trace": None}) is None


def test_crossings_reader_reads_the_window_counters():
    read = metric_reader("crossings_per_refresh")
    rec = {"counters": {"event_dispatches": 30, "tick_dispatches": 10,
                        "h2d": 480, "d2h": 120}}
    assert read(rec) == pytest.approx(15.0)
    assert read({"counters": {}}) is None       # a program without them
    assert read({"counters": dict(rec["counters"], event_dispatches=0,
                                  tick_dispatches=0)}) is None


def test_counters_are_differenced_over_the_window():
    """What the harness stores: the counters at the close less those at
    the open, read off the scheduler (empty where it has none)."""
    import types
    from bench import harness
    sched = types.SimpleNamespace(refresh_stats={"h2d": 5, "d2h": 2})
    sim = types.SimpleNamespace(sched=sched)
    c0 = harness.program_counters(sim)
    sched.refresh_stats["h2d"] += 7
    sched.refresh_stats["d2h"] += 3
    assert harness.window_counters(c0, harness.program_counters(sim)) == \
        {"h2d": 7, "d2h": 3}
    bare = types.SimpleNamespace(sched=types.SimpleNamespace())
    assert harness.program_counters(bare) == {}


@pytest.mark.parametrize("kernel", ["pdgraph_walk_ranked", "pdgraph_walk"])
def test_named_kernel_is_labelled_and_read(kernel):
    op = (f"%{kernel}.1 = (f32[128,10]) custom-call(f32[1000,40] "
          '%bitcast.155), custom_call_target="tpu_custom_call"')
    (label, _, _), = tr.label_ops([(op, 10, 5)],
                                  [("jit__delta_pipeline(1234)", 9, 10)])
    assert label == f"jit__delta_pipeline/{kernel}.1 tpu_custom_call"
    rec = {"trace": {"op_s": {label: 5e-6, "jit_x/fusion.1": 1e-6}},
           "rows_walked": 2}
    assert metric_reader("walk_device_us_per_row")(rec) == pytest.approx(2.5)
