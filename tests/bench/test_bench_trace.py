"""The trace reduction on a small recorded trace: busy union, idle share,
per-operation sums, and idle gaps labelled by the harness's host spans."""
import pytest

from bench import trace_reduce as tr

KERNEL = "jit__delta_pipeline/_delta_pipeline.1 tpu_custom_call"


def test_union_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (29, 31)]
    assert tr.union_ns(iv) == 15 + 11
    assert tr.gaps(iv, 0, 40) == [(15, 20), (31, 40)]
    assert tr.gaps(iv, 12, 25) == [(15, 20)]
    assert tr.union_ns([]) == 0.0


def _trace():
    # window 1,000 ns; device busy 0-100, 150-250 (two overlapping ops),
    # 600-700; host spans: a full tick over 100-400, a drain inside it
    # 120-140 ... and nothing over 700-1,000
    device = {"/device:TPU:0": [(KERNEL, 0, 100),
                                ("fusion.1", 150, 100),
                                (KERNEL, 200, 50),
                                ("fusion.2", 600, 100),
                                ("fusion.3", 1500, 100)]}
    host = [("bench.window", 0, 1000), ("bench.full_tick", 100, 300),
            ("bench.drain", 110, 30), ("bench.reschedule", 400, 300)]
    return device, host


def test_reduce_events_busy_idle_and_kernel():
    out = tr.reduce_events(*_trace())
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_s"] == pytest.approx(300e-9)      # 0-100,150-250,600-700
    assert out["n_ops"] == 4                           # fusion.3 is outside
    assert out["op_s"][KERNEL] == pytest.approx(150e-9)
    idle = dict(out["breakdown"]["idle_gaps"])
    # gaps: 100-150 (mid 125: inside the drain), 250-600 (mid 425:
    # reschedule), 700-1000 (mid 850: no span)
    assert idle["drain"] == pytest.approx(50e-9)
    assert idle["reschedule"] == pytest.approx(350e-9)
    assert idle["engine"] == pytest.approx(300e-9)
    ops = out["breakdown"]["device_ops"]
    assert ops[0][0] == KERNEL and len(ops) == 3


def test_reduce_events_averages_devices_and_needs_window():
    device, host = _trace()
    device["/device:TPU:1"] = [("fusion.9", 0, 1000)]
    out = tr.reduce_events(device, host)
    assert out["busy_s"] == pytest.approx((300e-9 + 1000e-9) / 2)
    with pytest.raises(ValueError):
        tr.reduce_events(device, [("bench.drain", 0, 10)])


def test_device_idle_and_kernel_readers():
    from bench.harness import metric_reader
    rec = {"trace": tr.reduce_events(*_trace()), "rows_walked": 3}
    assert metric_reader("device_idle_pct")(rec) == pytest.approx(70.0)
    kernel = metric_reader("walk_device_us_per_row")
    assert kernel(rec) == pytest.approx(150e-9 / 3 * 1e6)
    rec["rows_walked"] = 0
    assert kernel(rec) is None                       # nothing to read
    assert metric_reader("device_idle_pct")({"trace": None}) is None


def test_ops_are_labelled_by_program_and_kernel():
    kern = ('%_delta_pipeline.1 = (f32[128,10]) custom-call(f32[1000,40] '
            '%bitcast.155), custom_call_target="tpu_custom_call"')
    ops = [(kern, 10, 5), ("%fusion.3 = f32[8] fusion(f32[8] %x)", 16, 2),
           ("%copy.1 = f32[8] copy(f32[8] %y)", 40, 1)]
    mods = [("jit__delta_pipeline(1234)", 9, 10), ("jit_convert(99)", 39, 3)]
    got = tr.label_ops(ops, mods)
    assert [g[0] for g in got] == [
        "jit__delta_pipeline/_delta_pipeline.1 tpu_custom_call",
        "jit__delta_pipeline/fusion.3", "jit_convert/copy.1"]
    assert tr.label_ops([("%a = f32[] add()", 100, 1)], mods)[0][0] == "?/a"
