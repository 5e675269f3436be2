"""The comparison that decides ``correct``.

Three things are compared, once the window has closed:

* the refresh: a sample, drawn from the seed, of the window's refresh
  dispatches (every dispatch is an answer: the histogram rows of the rows
  it walked, scattered into the device arena, and the ranks of the whole
  arena).  For each sampled dispatch the plain reference
  (``bench/reference.py``) walks the same applications from their state
  as the simulator and the scheduler's application records held it at the
  dispatch (unit from the simulator's trajectory position, refinement
  overrides, walk counters, and the service credited through the
  scheduler's ``on_progress`` events, summed by the harness), and ranks
  the arena from its own rows for the walked slots and the arena's rows
  for the rest.  Where the dispatch returned the deadline triage
  (Hermes-DDL), the reference's triage of the walked rows is compared,
  and so is the class of every live row: the program's is the key the
  simulator ranks its waiting queues by after the refresh, over 10**6;
  the reference's comes from its own triage for the walked rows and the
  arena's for the rest, the trace's deadline, the dispatch's time and the
  credited service;
* the dirty set: at every dispatch of the window that drains the whole
  dirty set (the scheduler's call without ids), each live application
  that was admitted, started or finished a unit, or was requeued since
  its last walk has to be walked by that dispatch;
* the engine: at the close, no slot of a pool is free while work of its
  kind waits, and no waiting task holds a better key than a running task
  that could be preempted.

Numbers compared (each with its limit in ``bench/limits.json``):

``edge_gap``      the widest relative gap between a walked row's bucket
                  edges and the reference's;
``rank_gap``      the widest relative gap between the dispatch's ranks and
                  the reference's, over every live row of the arena;
``triage_gap``    the widest relative gap between a walked row's triage
                  scalars (P90, P10, mean) and the reference's (0 on a
                  dispatch without triage);
``class_flips``   live rows whose deadline class differs from the
                  reference's, leaving out rows whose reference slack lies
                  within ``CLASS_MARGIN_S`` of a class boundary (exact: 0);
``stale_rows``    live applications a draining dispatch of the window left
                  unwalked although their position moved (exact: 0);
``dispatch_violations``  the engine's breaches at the close (exact: 0).

``walker_moves`` (the most walkers any walked row has in another bucket
than the reference puts them) is printed beside them, not compared: a
moved walker shows in the row's rank.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from bench import reference

PER_DISPATCH = ("edge_gap", "rank_gap", "triage_gap", "class_flips")
RUN_RULES = ("stale_rows", "dispatch_violations")  # counted by the harness
NUMBERS = PER_DISPATCH + RUN_RULES
COUNTS = ("class_flips",)                          # summed over dispatches
CLASS_SPAN = 1e6          # the simulator's key is class * 10**6 + rank
# The program's class takes the service credited in float64, the
# reference's the float32 sums the arena keeps; over some hundred events
# at up to 10**4 s the two part by a few milliseconds, and float32 P90 /
# P10 values at that scale round at about 1e-3 s.  A row whose reference
# slack lies within this margin of 0 or of the risk window could flip on
# rounding alone, so it is not counted.
CLASS_MARGIN_S = 0.05


@dataclasses.dataclass
class Capture:
    """One refresh dispatch as the window ran it."""
    kind: str                       # "tick" | "event"
    walked: np.ndarray              # slots walked
    apps: List[Dict]                # each walked row's application record
    d_probs: object                 # arena rows after the dispatch (device)
    d_edges: object
    ranks: np.ndarray               # (capacity,) ranks it returned
    attained: np.ndarray            # (capacity,) credited service per slot
    occupied: np.ndarray            # slots live at the dispatch
    # with the deadline triage (None without):
    triage: Optional[np.ndarray] = None     # (3, walked) sup, opt, mean
    arena_triage: Optional[np.ndarray] = None   # (2, capacity) sup, opt
    deadline: Optional[np.ndarray] = None   # (capacity,) from the trace
    now: float = 0.0                        # simulated time of the dispatch
    key_class: Optional[np.ndarray] = None  # (capacity,) the program's class


def _rel(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    same = (a == b) | (np.isinf(a) & np.isinf(b) & (np.sign(a) == np.sign(b)))
    with np.errstate(invalid="ignore"):
        gap = np.abs(a - b) / np.maximum(np.abs(b), 1e-6)
    return np.where(same, 0.0, np.where(np.isfinite(gap), gap, np.inf))


def compare_capture(cap: Capture, tables: Dict, *, seed: int, n_walkers: int,
                    n_buckets: int, precision: str = "highest") -> Dict:
    """Numbers of one dispatch against the reference.  With
    ``precision="high"`` the control (the reference at the lower
    precision) takes the program's place: it is compared against the
    reference at full precision on the same inputs."""
    rows = reference.rows_from_apps(tables, cap.apps)
    tri = cap.triage is not None
    kw = dict(seed=seed, n_walkers=n_walkers, n_buckets=n_buckets,
              with_triage=tri)
    ref_p, ref_e, *ref_t = reference.refresh_rows(tables, rows, **kw)
    arena_p = np.asarray(cap.d_probs, np.float32)
    arena_e = np.asarray(cap.d_edges, np.float32)
    ranks = np.asarray(cap.ranks, np.float32)
    if precision == "highest":
        got_p, got_e = arena_p[cap.walked], arena_e[cap.walked]
        got_t = [cap.triage] if tri else []
    else:
        got_p, got_e, *got_t = reference.refresh_rows(
            tables, rows, precision=precision, **kw)
        arena_p, arena_e = arena_p.copy(), arena_e.copy()
        arena_p[cap.walked], arena_e[cap.walked] = got_p, got_e
        occ = cap.occupied
        ranks = ranks.copy()
        ranks[occ] = reference.gittins(arena_p[occ], arena_e[occ],
                                       cap.attained[occ])
    moves = (np.abs(got_p.astype(np.float64) - ref_p).sum(axis=1)
             * n_walkers / 2.0) if len(ref_p) else np.zeros(0)
    egap = _rel(got_e, ref_e).max(axis=1) if len(ref_e) else np.zeros(0)
    ref_arena_p, ref_arena_e = arena_p.copy(), arena_e.copy()
    ref_arena_p[cap.walked], ref_arena_e[cap.walked] = ref_p, ref_e
    occ = cap.occupied
    want = reference.gittins(ref_arena_p[occ], ref_arena_e[occ],
                             cap.attained[occ])
    rgap = _rel(ranks[occ], want)
    out = {"walker_moves": float(moves.max()) if len(moves) else 0.0,
           "edge_gap": float(egap.max()) if len(egap) else 0.0,
           "rank_gap": float(rgap.max()) if len(rgap) else 0.0,
           "triage_gap": 0.0, "class_flips": 0.0,
           "rows": int(len(cap.walked)), "arena_rows": int(len(occ))}
    if tri:
        out.update(_compare_triage(cap, np.asarray(ref_t[0]),
                                   np.asarray(got_t[0]), precision))
    if len(rgap):
        i = int(np.argmax(rgap))
        s = int(occ[i])
        out["worst_rank_row"] = {
            "kind": cap.kind, "slot": s, "walked": bool(s in cap.walked),
            "rank": float(ranks[s]), "reference": float(want[i]),
            "attained": float(cap.attained[s]),
            "probs": arena_p[s].tolist(), "edges": arena_e[s].tolist()}
    return out


def _compare_triage(cap: Capture, ref_t: np.ndarray, got_t: np.ndarray,
                    precision: str) -> Dict:
    """``triage_gap`` of the walked rows and ``class_flips`` of the live
    rows of one dispatch with triage.  The reference's class takes its own
    P90 / P10 for the walked rows and the arena's for the rest; the
    control's (``precision`` not ``"highest"``) takes its own for the
    walked rows, the program's class is read off its key."""
    occ = cap.occupied
    tgap = _rel(got_t, ref_t).max() if ref_t.size else 0.0

    def classes(walked_t):
        sup, opt = np.array(cap.arena_triage)
        sup[cap.walked], opt[cap.walked] = walked_t[0], walked_t[1]
        args = (sup[occ], opt[occ], cap.attained[occ], cap.deadline[occ],
                cap.now)
        return reference.ddl_class(*args), reference.slacks(*args)
    want, (s_sup, s_opt) = classes(ref_t)
    got = cap.key_class[occ] if precision == "highest" else classes(got_t)[0]
    m = CLASS_MARGIN_S
    near_opt = np.abs(s_opt) < m
    near_sup = (s_opt >= m) & (np.abs(s_sup - reference.RISK_WINDOW_S) < m)
    flips = (got != want) & ~(near_opt | near_sup)
    return {"triage_gap": float(tgap), "class_flips": float(flips.sum())}


def dispatch_violations(sim) -> int:
    """Breaches of the dispatch rule at the close: a free slot while work
    of its kind waits, or (preemptive) a waiting key below the key of the
    worst running task that has finished warming up."""
    bad = 0
    for kind in sim.slots:
        wq = sim.waiting[kind]
        if not len(wq):
            continue
        k0, k1, k2, _, tasks = wq._gather()
        keys = sorted(zip(k0.tolist(), k1.tolist(), k2.tolist()))
        running = list(sim.running[kind])
        if len(running) < sim.pools[kind].capacity():
            bad += 1
        if sim.cfg.preemptive and running:
            victim = max(running, key=sim._task_rank)
            if victim.ready_at <= sim.now and \
                    keys[0] < sim._task_rank(victim):
                bad += 1
    return bad


def combine(parts: List[Dict]) -> Dict[str, float]:
    """The widest reading of each gap, and the sum of each count, over the
    sampled dispatches."""
    out = {k: 0.0 for k in PER_DISPATCH + ("walker_moves",)}
    for p in parts:
        for k in out:
            out[k] = out[k] + p[k] if k in COUNTS else max(out[k], p[k])
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= limits[k] for k in NUMBERS)


def reservoir_slot(i: int, k: int, rng: np.random.Generator
                   ) -> Optional[int]:
    """Reservoir sampling over a stream: where call ``i`` (0-based) goes in
    a reservoir of ``k``, or None when it is not kept."""
    if i < k:
        return i
    j = int(rng.integers(0, i + 1))
    return j if j < k else None
