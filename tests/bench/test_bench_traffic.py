"""The benchmark's copy of the traffic generator still gives what the
program's ``make_open_workload`` gives for the same seed, deadlines
included; under a set seed each deadline moves with its arrival."""
import hashlib

import pytest

from bench import traffic_gen
from repro.apps.suite import T_IN, T_OUT
from repro.apps.workload import make_open_workload


@pytest.mark.parametrize("seed,load,slots", [(3, 1.0, 16), (11, 0.8, 64)])
def test_copy_matches_make_open_workload(seed, load, slots):
    kw = dict(t_in=T_IN, t_out=T_OUT, target_load=load,
              n_service_slots=slots, process="gamma", cv=2.5, tenants=16,
              seed=seed)
    want = make_open_workload(600.0, **kw)
    got, _ = traffic_gen.open_workload(600.0, **kw)
    assert len(want) > 20
    assert traffic_gen.same_trace(got, want)
    assert [g.app_id for g in got] == [w.app_id for w in want]


def test_generate_burst_and_stream():
    traffic = {"stream": {"process": "gamma", "cv": 2.5, "tenants": 16,
                          "load": 1.0, "duration_s": 300.0},
               "burst": {"n_apps": 64, "waves": 4, "span_s": 8.0},
               "demand_probe": {"n_probe": 50, "seed": 0}}
    a = traffic_gen.generate(traffic, n_llm_slots=16, t_in=T_IN,
                             t_out=T_OUT, seed=2 ** 35 + 1)
    b = traffic_gen.generate(traffic, n_llm_slots=16, t_in=T_IN,
                             t_out=T_OUT, seed=2 ** 35 + 1)
    assert traffic_gen.same_trace(a["instances"], b["instances"])
    crowd = [i for i in a["instances"] if i.app_id.startswith("crowd")]
    assert len(crowd) == 64
    assert sorted({i.arrival for i in crowd}) == [1.0, 3.0, 5.0, 7.0]
    arr = [i.arrival for i in a["instances"]]
    assert arr == sorted(arr)
    # the offered rate is fixed by the file, not by the run's seed
    c = traffic_gen.generate(traffic, n_llm_slots=16, t_in=T_IN,
                             t_out=T_OUT, seed=5)
    assert c["rate_per_s"] == a["rate_per_s"]
    assert not traffic_gen.same_trace(c["instances"], a["instances"])
    # with a set seed every run seed offers the same arrivals and the same
    # applications, in another order
    traffic["stream"]["set_seed"] = 0
    del traffic["burst"]
    d, e = (traffic_gen.generate(traffic, n_llm_slots=16, t_in=T_IN,
                                 t_out=T_OUT, seed=s)["instances"]
            for s in (5, 6))
    assert [i.arrival for i in d] == [i.arrival for i in e]
    assert [i.app_name for i in d] != [i.app_name for i in e]
    assert sorted(i.app_name for i in d) == sorted(i.app_name for i in e)
    assert sorted(map(repr, (i.trajectory for i in d))) == \
        sorted(map(repr, (i.trajectory for i in e)))


DEADLINES = {"scales": [1.2, 1.5, 2.0], "share": 1.0}


@pytest.mark.parametrize("seed,load", [(3, 1.0), (11, 0.8)])
def test_deadlines_match_make_open_workload(seed, load):
    kw = dict(t_in=T_IN, t_out=T_OUT, target_load=load, n_service_slots=16,
              process="gamma", cv=2.5, tenants=16, seed=seed)
    want = make_open_workload(900.0, with_deadlines=True, **kw)
    got, _ = traffic_gen.open_workload(900.0, deadlines=DEADLINES, **kw)
    assert len(want) > 20 and all(w.deadline is not None for w in want)
    assert [g.deadline for g in got] == [w.deadline for w in want]
    assert traffic_gen.same_trace(got, want)
    # without the section no application carries a deadline, and the
    # rest of the trace is the same
    plain, _ = traffic_gen.open_workload(900.0, **kw)
    assert all(p.deadline is None for p in plain)
    assert traffic_gen.same_trace(plain,
                                  make_open_workload(900.0, **kw))


def _digest(insts):
    h = hashlib.sha256()
    for i in insts:
        h.update(repr((i.app_id, i.app_name, i.tenant, i.arrival,
                       i.trajectory, i.deadline, i.ddl_class)).encode())
    return h.hexdigest()


def test_steady_trace_is_unchanged():
    """The steady mix's trace for one seed, as the generator gave it before
    deadlines could be asked for."""
    from bench.harness import BENCH, load_json
    traffic = load_json(BENCH / "traffic" / "steady.json")
    got = traffic_gen.generate(traffic, n_llm_slots=16, t_in=T_IN,
                               t_out=T_OUT, seed=2 ** 40 + 15)["instances"]
    assert len(got) == 3299
    assert _digest(got) == \
        "49ff1102efcb2fdc5a12df812f5f4972717daa234ee2311beee8d1bb8475b1d6"


def test_shuffle_keeps_each_deadline_offset():
    traffic = {"stream": {"process": "gamma", "cv": 2.5, "tenants": 16,
                          "load": 1.0, "duration_s": 900.0, "set_seed": 4},
               "deadlines": DEADLINES,
               "demand_probe": {"n_probe": 50, "seed": 0}}
    rate = traffic_gen.generate(traffic, n_llm_slots=16, t_in=T_IN,
                                t_out=T_OUT, seed=1)["rate_per_s"]
    base, _ = traffic_gen.open_workload(900.0, t_in=T_IN, t_out=T_OUT,
                                        rate_per_s=rate, process="gamma",
                                        cv=2.5, tenants=16, seed=4,
                                        deadlines=DEADLINES)
    offset = {}
    for b in base:
        offset.setdefault((b.app_name, repr(b.trajectory)), []).append(
            b.deadline - b.arrival)
    runs = [traffic_gen.generate(traffic, n_llm_slots=16, t_in=T_IN,
                                 t_out=T_OUT, seed=s)["instances"]
            for s in (5, 6)]
    assert [i.arrival for i in runs[0]] == [b.arrival for b in base]
    assert [i.app_name for i in runs[0]] != [i.app_name for i in runs[1]]
    for insts in runs:
        for i in insts:
            assert any(i.deadline - i.arrival == pytest.approx(o, rel=1e-9)
                       for o in offset[(i.app_name, repr(i.trajectory))])
