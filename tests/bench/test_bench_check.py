"""Pieces of the check: the dispatch sample and the engine's rule."""
import types

import numpy as np

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
