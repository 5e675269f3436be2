"""Configurations, traffic mixes and metrics are found by file name, and
BENCHMARK.json keeps to the shape the harness reads."""
import json
import re

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BM = harness.load_benchmark()


@pytest.mark.parametrize("cell", [c["name"] for c in BM["workloads"]])
def test_each_cell_finds_its_files(cell):
    c, config, traffic, e2e, layer, units = harness.cell_parts(BM, cell)
    assert config["name"] == c["config"]
    assert traffic["name"] == c["traffic"]
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for k in ("n_llm_slots", "mc_walkers", "n_buckets"):
        assert k in config["sim"]
    assert {"min_sim_s", "quiet_ticks"} <= set(traffic["warmup"])


def test_names_files_and_arrows():
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    names += [c["name"] for c in BM["workloads"] + BM["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BM["end_to_end"]}
    cells = {c["name"] for c in BM["workloads"]}
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for cfg in BM["configs"]:
        path = harness.ROOT / cfg["file"]
        assert path.is_file() and cfg["file"].startswith(tuple(BM["paths"]))
        assert json.loads(path.read_text())["name"] == cfg["name"]
    assert any(m["name"] == "setup_s" for m in BM["end_to_end"])


def test_unknown_cell_is_refused():
    with pytest.raises(harness.RunError):
        harness.cell_parts(BM, "no.such.cell")


def test_limits_cover_the_compared_numbers():
    from bench import check
    assert set(harness.load_limits()) == set(check.NUMBERS)


def test_seeds_take_any_whole_number():
    a = harness.seeds_from(2 ** 40 + 3)
    assert a == harness.seeds_from(2 ** 40 + 3)
    assert a != harness.seeds_from(2 ** 40 + 4)
    assert all(0 <= v < 2 ** 32 for v in a.values())
