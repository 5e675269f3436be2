"""The program's own trace marks seen by the trace reduction: its host
spans (``hermes.*``) nested inside the harness's leave every reading as it
was, and the walk kernels, named on the device trace, are still the
kernel that ``walk_device_us_per_row`` reads."""
import pytest

from bench import trace_reduce as tr
from bench.harness import metric_reader


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
    assert spanned == plain
    assert dict(plain["breakdown"]["idle_gaps"]) == pytest.approx(
        {"event_refresh": 500e-9, "engine": 300e-9})
    rec = {"trace": spanned, "rows_walked": 1}
    assert metric_reader("device_idle_pct")(rec) == pytest.approx(80.0)


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
