"""The deadline deployment's cell, ``ddl.steady``: its files are found by
name and hold the testbed under the Hermes-DDL policy and the steady
stream with a deadline on every application; a tiny run of that shape on
the CPU is correct and names its dispatches as the harness classes them;
the two readers of the rank consumption read the program's spans."""
import pytest

from bench import harness

BM = harness.load_benchmark()
LAYER = ["engine_us_per_event", "event_refresh_ms", "walked_rows_per_tick",
         "walk_device_us_per_row", "device_idle_pct", "event_prepare_ms",
         "event_wait_ms", "event_consume_ms", "tick_prepare_ms",
         "tick_wait_ms", "tick_consume_ms", "crossings_per_refresh",
         "key_ms", "rekey_ms"]


def test_cell_finds_the_ddl_files():
    cell, config, traffic, e2e, layer, units = harness.cell_parts(
        BM, "ddl.steady")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("hermes_ddl", "steady_ddl", 1)
    assert set(e2e) == {"batch_p99_ms", "tick_ms", "setup_s"}
    assert layer == LAYER
    assert units["key_ms"] == units["rekey_ms"] == "ms"
    # the testbed under the deadline policy: nothing else differs
    testbed = harness.load_json(harness.BENCH / "configs" /
                                "hermes_testbed.json")
    assert config["sim"] == dict(testbed["sim"], policy="hermes_ddl")
    for k in ("refresh", "knowledge_base"):
        assert config[k] == testbed[k]
    steady = harness.load_json(harness.BENCH / "traffic" / "steady.json")
    assert traffic["deadlines"] == {"scales": [1.2, 1.5, 2.0], "share": 1.0}
    for k in ("stream", "demand_probe", "warmup"):
        assert traffic[k] == steady[k]
    # the new readers cover both cells
    for m in BM["per_layer"]:
        if m["name"] in ("key_ms", "rekey_ms"):
            assert m["workloads"] == ["testbed.steady", "ddl.steady"]
            assert m["layer"] == "rank consumption"


def _tiny(traffic):
    """The cell's stream cut to a CPU run: a shorter trace, a small probe,
    a short warm-up and arena."""
    return dict(traffic, stream=dict(traffic["stream"], duration_s=3000.0),
                demand_probe={"n_probe": 50, "seed": 0},
                warmup={"min_sim_s": 60.0, "quiet_ticks": 1,
                        "max_rows": 16})


def test_tiny_ddl_steady_run():
    _, config, traffic, _, _, _ = harness.cell_parts(BM, "ddl.steady")

    def steer(sim):
        sim.sched.compact_after = 64       # no compaction stage: no spill

    rec = harness.run_cell(config, _tiny(traffic), seed=2 ** 35 + 16,
                           seconds=1.5, trace=False, require_tpu=False,
                           compile_cache=False, steer=steer)
    assert rec["correct"], rec["check"]
    assert rec["spilled"] == 0 and rec["checked"]["dispatches"] > 0
    assert rec["check"]["class_flips"] == 0.0
    assert 0.0 < rec["check"]["triage_gap"] <= rec["limits"]["triage_gap"]
    # the program names each dispatch by its caller, as the harness does
    c = rec["counters"]
    assert c["tick_dispatches"] == rec["bucket_ticks"] == \
        len(rec["tick_rows"]) > 0
    assert c["event_dispatches"] + c["tick_dispatches"] == rec["attempted"]
    assert c["event_dispatches"] > 0


def _spans(**spans):
    return {"trace": {"spans": {f"hermes.{k}": {"n": n, "s": s}
                                for k, (n, s) in spans.items()}}}


@pytest.mark.parametrize("name,span", [("key_ms", "key"),
                                       ("rekey_ms", "rekey")])
def test_rank_consumption_readers(name, span):
    read = harness.metric_reader(name)
    rec = _spans(**{span: (4, 0.002), "event.wait": (9, 1.0)})
    assert read(rec) == pytest.approx(0.5)
    # no trace, no such span (a program without it), or none in the window
    assert read({"trace": None}) is None
    assert read(_spans(**{"event.wait": (9, 1.0)})) is None
    assert read(_spans(**{span: (0, 0.0)})) is None
