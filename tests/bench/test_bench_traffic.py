"""The benchmark's copy of the traffic generator still gives what the
program's ``make_open_workload`` gives for the same seed."""
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
    got = traffic_gen.open_workload(600.0, **kw)
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
