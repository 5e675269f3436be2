"""A tiny cell driven through the measurement core on the CPU, with the
test steering it: the refresh runs the kernel's bit-identical jnp twin
with compaction off (the chip's kernel walks every walker in one phase),
the persistent compile cache is left alone.  A sound run comes out
correct; with the timed path broken underneath it comes out not correct,
once for each fault the cell can have: a dispatch that leaves the arena
as it was, one that scatters half of its walked rows, one that alters
the ranks it returns, a full tick that walks half of the dirty set, and
an arena that marks only half of its slots dirty.

The same cell under the deadline policy (Hermes-DDL, every application
with a deadline) checks the triage and the deadline class as well: a
sound run is correct and its control is not, a triage off by 1% and one
application's class moved in the program alone are not correct."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from repro.core import refresh_pipeline
from repro.core.arena import QueueState
from repro.core.policies import HermesDDLPolicy

CONFIG = harness.load_json(harness.BENCH / "configs" / "hermes_testbed.json")
TRAFFIC = {"name": "tiny",
           "stream": {"process": "gamma", "cv": 2.5, "tenants": 4,
                      "load": 1.0, "duration_s": 1500.0},
           "burst": {"n_apps": 24, "waves": 2, "span_s": 2.0},
           "demand_probe": {"n_probe": 50, "seed": 0},
           "warmup": {"min_sim_s": 3.0, "quiet_ticks": 1, "max_rows": 16}}
DDL_CONFIG = dict(CONFIG, sim=dict(CONFIG["sim"], policy="hermes_ddl"))
DDL_TRAFFIC = dict(TRAFFIC, deadlines={"scales": [1.2, 1.5, 2.0],
                                       "share": 1.0})


def steer(sim):
    sim.sched.compact_after = 64       # no compaction stage: no spill


def _state_unchanged(orig):
    def fn(*a, **k):
        out = orig(*a, **k)
        return (a[16], a[17]) + tuple(out[2:])
    return fn


def _half_batch(orig):
    def fn(*a, **k):
        a = list(a)
        valid, slot_idx = np.asarray(a[13]), np.asarray(a[15]).copy()
        d = int(valid.sum())
        slot_idx[d // 2:d] = a[16].shape[0]       # dropped by the scatter
        a[15] = jnp.asarray(slot_idx)
        return orig(*a, **k)
    return fn


def _answer_altered(orig):
    def fn(*a, **k):
        out = orig(*a, **k)
        return tuple(out[:2]) + (out[2] * 1.01,) + tuple(out[3:])
    return fn


def _dirty_half(orig):
    def take(self):
        walked = orig(self)
        return walked[len(walked) // 2:]
    return take


def _marks_half(orig):
    def add(self, slot):
        if slot % 2 == 0:
            orig(self, slot)
    return add


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered, "dirty_half": _dirty_half,
          "marks_half": _marks_half}
ARENA_FAULTS = {"dirty_half": "take_dirty", "marks_half": "_add_dirty"}


def _triage_off(orig):
    def fn(total):
        return tuple(x * 1.01 for x in orig(total))
    return fn


def _class_moved(orig):
    """Move the first row's deadline across a class boundary: to no
    deadline (safe) where it is hopeless, else into the past (hopeless)."""
    def fn(self, now, *, opt, attained, deadline, **kw):
        deadline = np.array(deadline, np.float64)
        late = deadline[0] - now - max(opt[0] - attained[0], 0.0) < 0.0
        deadline[0] = np.inf if late else now - 1e6
        return orig(self, now, opt=opt, attained=attained,
                    deadline=deadline, **kw)
    return fn


def _run(monkeypatch, fault=None, control=False, seed=2 ** 33 + 7,
         ddl=False):
    def plant(sim):
        if fault in ARENA_FAULTS:
            name = ARENA_FAULTS[fault]
            monkeypatch.setattr(QueueState, name,
                                FAULTS[fault](getattr(QueueState, name)))
        elif fault == "class_moved":
            monkeypatch.setattr(HermesDDLPolicy, "ranks_columns",
                                _class_moved(HermesDDLPolicy.ranks_columns))
        elif fault is not None and fault != "triage_off":
            monkeypatch.setattr(refresh_pipeline, "_delta_pipeline",
                                FAULTS[fault](
                                    refresh_pipeline._delta_pipeline))

    def steer_sim(sim):
        steer(sim)
        if fault == "triage_off":
            # traced into the compiled dispatch: planted before the
            # set-up compiles it, with no program of an earlier run cached
            jax.clear_caches()
            monkeypatch.setattr(refresh_pipeline, "_triage_stats",
                                _triage_off(refresh_pipeline._triage_stats))
    try:
        return harness.run_cell(DDL_CONFIG if ddl else CONFIG,
                                DDL_TRAFFIC if ddl else TRAFFIC, seed=seed,
                                seconds=1.5, trace=False, require_tpu=False,
                                compile_cache=False, steer=steer_sim,
                                fault=plant, control=control)
    finally:
        if fault == "triage_off":
            jax.clear_caches()


@pytest.mark.parametrize("case", ["sound"] + sorted(FAULTS))
def test_tiny_cell(monkeypatch, case):
    rec = _run(monkeypatch, None if case == "sound" else case,
               control=case == "sound")
    assert rec["attempted"] > 0 and rec["checked"]["dispatches"] > 0
    assert rec["checked"]["rows"] > 0
    assert rec["spilled"] == 0
    assert len(rec["batches"]) > 0 and rec["events"] > 0
    if case == "sound":
        assert rec["correct"], rec["check"]
        assert rec["check"]["walker_moves"] == 0.0
        assert rec["check"]["edge_gap"] == rec["check"]["rank_gap"] == 0.0
        assert rec["check"]["stale_rows"] == 0.0
        # Gittins: no triage, and every tick dispatch is a bucket tick's
        assert rec["check"]["triage_gap"] == rec["check"]["class_flips"] == 0
        assert len(rec["tick_rows"]) == rec["bucket_ticks"] > 0
        assert rec["counters"]["tick_dispatches"] == rec["bucket_ticks"]
        assert rec["counters"]["event_dispatches"] + \
            rec["counters"]["tick_dispatches"] == rec["attempted"]
        # the control, in the program's place, is not correct
        assert not harness.check.verdict(
            dict(rec["control"], stale_rows=0.0, dispatch_violations=0.0),
            rec["limits"])
        line = harness.result_line(rec, ["batch_p99_ms", "setup_s"],
                                   {"batch_p99_ms": "ms", "setup_s": "s"},
                                   False)
        assert list(line)[-1] == "checks"
        assert set(line["metrics"]) == {"batch_p99_ms", "setup_s"}
    else:
        assert not rec["correct"], (case, rec["check"])


@pytest.mark.parametrize("case", ["sound", "triage_off", "class_moved"])
def test_tiny_ddl_cell(monkeypatch, case):
    rec = _run(monkeypatch, None if case == "sound" else case,
               control=case == "sound", seed=2 ** 34 + 11, ddl=True)
    assert rec["attempted"] > 0 and rec["checked"]["dispatches"] > 0
    assert rec["spilled"] == 0
    # an event batch's full re-rank is an event dispatch: the window's
    # tick dispatches are its bucket ticks
    assert len(rec["tick_rows"]) == rec["bucket_ticks"] > 0
    assert len(rec["event_refresh_s"]) == 0     # untimed run
    assert rec["attempted"] > rec["bucket_ticks"]
    limits = rec["limits"]
    if case == "sound":
        assert rec["correct"], rec["check"]
        assert 0.0 < rec["check"]["triage_gap"] <= limits["triage_gap"]
        assert rec["check"]["class_flips"] == 0.0
        assert rec["check"]["edge_gap"] == rec["check"]["stale_rows"] == 0.0
        ctl = dict(rec["control"], stale_rows=0.0, dispatch_violations=0.0)
        assert ctl["triage_gap"] > limits["triage_gap"]
        assert not harness.check.verdict(ctl, limits)
    elif case == "triage_off":
        assert not rec["correct"]
        assert rec["check"]["triage_gap"] > limits["triage_gap"]
    else:
        assert not rec["correct"]
        assert rec["check"]["class_flips"] > 0
        assert rec["check"]["triage_gap"] <= limits["triage_gap"]
