"""The metric arithmetic: a p99 over every micro-batch, a tick's mean over
all tick batches, the engine's self time over the whole window."""
import numpy as np
import pytest

from bench.harness import load_benchmark, metric_reader


def _rec(**kw):
    rec = {"window_s": 10.0, "events": 2500, "setup_s": 42.5,
           "batches": np.linspace(0.001, 0.1, 1000),
           "tick_batches": np.asarray([0.1, 0.2, 0.3]),
           "policy_s": 4.0, "event_refresh_s": np.asarray([0.002, 0.004]),
           "tick_rows": np.asarray([10, 20, 60]), "rows_walked": 90,
           "trace": None}
    rec.update(kw)
    return rec


def test_p99_over_every_batch():
    b = np.concatenate([np.full(990, 0.001), np.full(10, 0.5)])
    got = metric_reader("batch_p99_ms")(_rec(batches=b))
    assert got == pytest.approx(np.percentile(b, 99) * 1e3)
    assert metric_reader("batch_p99_ms")(_rec(batches=np.zeros(0))) is None


def test_tick_ms_is_a_total_over_a_count():
    assert metric_reader("tick_ms")(_rec()) == pytest.approx(200.0)
    assert metric_reader("tick_ms")(_rec(tick_batches=np.zeros(0))) is None


def test_engine_self_time_and_layer_means():
    rec = _rec()
    assert metric_reader("engine_us_per_event")(rec) == \
        pytest.approx((10.0 - 4.0) / 2500 * 1e6)
    assert metric_reader("event_refresh_ms")(rec) == pytest.approx(3.0)
    assert metric_reader("walked_rows_per_tick")(rec) == pytest.approx(30.0)
    assert metric_reader("setup_s")(rec) == 42.5


def test_every_metric_has_a_reader():
    bm = load_benchmark()
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(metric_reader(m["name"])), m["name"]
