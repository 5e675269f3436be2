"""Pieces of the check: the dispatch sample, the engine's rule, the
deadline class and how the numbers of many dispatches combine."""
import types

import numpy as np
import pytest

from bench import check


def test_reservoir_keeps_k_and_is_seeded():
    def draw(seed, n=500, k=8):
        rng = np.random.default_rng(seed)
        res = [None] * k
        for i in range(n):
            slot = check.reservoir_slot(i, k, rng)
            if slot is not None:
                res[slot] = i
        return res
    a = draw(1)
    assert a == draw(1) and a != draw(2)
    assert len(set(a)) == 8 and max(a) >= 8      # later calls get in too


class _Queue:
    def __init__(self, keys):
        self.keys = keys

    def __len__(self):
        return len(self.keys)

    def _gather(self):
        k = np.asarray(self.keys, float).reshape(-1, 3)
        return k[:, 0], k[:, 1], k[:, 2], None, [None] * len(self.keys)


def _sim(waiting, running, cap, ready_at=0.0):
    tasks = {t: None for t in running}
    return types.SimpleNamespace(
        slots={"llm": cap}, waiting={"llm": _Queue(waiting)},
        running={"llm": tasks},
        pools={"llm": types.SimpleNamespace(capacity=lambda: cap)},
        cfg=types.SimpleNamespace(preemptive=True), now=1.0,
        _task_rank=lambda t: (t.rank, 0.0, t.id))


class T:
    def __init__(self, rank, id, ready_at):
        self.rank, self.id, self.ready_at = rank, id, ready_at


def test_dispatch_rule():
    run = [T(rank=5.0, id=1, ready_at=0.0), T(rank=2.0, id=2, ready_at=0.0)]
    # full pool, waiting work ranks worse than every running task: sound
    assert check.dispatch_violations(_sim([(6.0, 0, 3)], run, 2)) == 0
    # a free slot while work waits
    assert check.dispatch_violations(_sim([(6.0, 0, 3)], run, 3)) == 1
    # a waiting key below the worst running task's
    assert check.dispatch_violations(_sim([(1.0, 0, 3)], run, 2)) == 1
    # ... unless that task is still warming up
    warming = [T(rank=5.0, id=1, ready_at=9.0), run[1]]
    assert check.dispatch_violations(_sim([(1.0, 0, 3)], warming, 2)) == 0
    assert check.dispatch_violations(_sim([], run, 3)) == 0


def _ddl_capture(now=100.0):
    # four live rows in slots 0-3, slot 0 walked; P10 and P90 of 50 and
    # 80 s of demand, 10 s served: the slack is deadline - now - 40 (P10)
    # and deadline - now - 70 (P90)
    occ = np.arange(4)
    sup = np.full(4, 80.0, np.float32)
    opt = np.full(4, 50.0, np.float32)
    deadline = now + np.asarray([200.0, 20.0, 99.0, 40.02])
    return check.Capture(
        kind="event", walked=np.asarray([0]), apps=[], d_probs=None,
        d_edges=None, ranks=np.zeros(4, np.float32),
        attained=np.full(4, 10.0, np.float32), occupied=occ,
        triage=np.asarray([[80.0], [50.0], [60.0]], np.float32),
        arena_triage=np.stack([sup, opt]), deadline=deadline, now=now,
        key_class=np.asarray([1, 2, 0, 1]))


def test_class_flips_count_rows_off_the_boundary():
    cap = _ddl_capture()
    ref = cap.triage.copy()
    # classes: 1 (safe), 2 (hopeless), 0 (29 s of P90 slack: at risk), and
    # a row 0.02 s from the hopeless boundary, left out either way
    out = check._compare_triage(cap, ref, cap.triage, "highest")
    assert out == {"triage_gap": 0.0, "class_flips": 0.0}
    cap.key_class = np.asarray([2, 2, 1, 2])
    out = check._compare_triage(cap, ref, cap.triage, "highest")
    assert out["class_flips"] == 2.0
    # the walked row's P90 off by 1%: a gap, and its class from the
    # reference's own triage
    ref[0, 0] = 80.0 / 1.01
    out = check._compare_triage(cap, ref, cap.triage, "highest")
    assert out["triage_gap"] == pytest.approx(0.01, rel=1e-5)


def test_combine_takes_the_widest_gap_and_sums_counts():
    parts = [dict(edge_gap=1e-7, rank_gap=0.0, triage_gap=2e-7,
                  class_flips=1.0, walker_moves=0.0),
             dict(edge_gap=0.0, rank_gap=3e-7, triage_gap=1e-7,
                  class_flips=2.0, walker_moves=1.0)]
    assert check.combine(parts) == dict(edge_gap=1e-7, rank_gap=3e-7,
                                        triage_gap=2e-7, class_flips=3.0,
                                        walker_moves=1.0)
    assert check.combine([])["class_flips"] == 0.0
