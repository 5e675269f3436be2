"""Plain reference of the Hermes refresh: the PDGraph Monte-Carlo walk,
the demand histogram, the Gittins rank, and the deadline triage of
Hermes-DDL (arXiv 2506.14851 §3.3), in numpy float32.

It imports nothing of the program.  Its walk tables are built here from
the knowledge base's raw profiling records (per-unit token counts,
parallelism, durations and branch counts), in the documented layout:
graphs in sorted name order, units in sorted name order within a graph,
padded to the widest graph.  The arithmetic follows the published
semantics of the counter-RNG walker (murmur3 finalizer over a per-walker
Weyl counter, one hash per step: demand-sample index from the high 16
bits, transition draw from the low 16), of the floor-binned histogram and
of the Gittins index over bucket midpoints, one float32 operation at a
time, each sum left to right.  The triage takes the P90 and P10 of each
row's walker totals by linear interpolation between order statistics, and
their mean; the class compares the slack those leave before the deadline
with nought and with the 30 s risk window.

``precision="high"`` is the control: every table value the walk selects
(sample, count, transition CDF) is first rounded to what a three-pass
bfloat16 product keeps (``hi + lo`` of two bfloat16 parts), as a one-hot
selection at ``Precision.HIGH`` instead of ``HIGHEST`` would.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

F32 = np.float32
U32 = np.uint32
M1 = U32(0x85EBCA6B)
M2 = U32(0xC2B2AE35)
GOLDEN = U32(0x9E3779B9)
U16_SCALE = F32(1.0 / 65536.0)
MAX_STEPS = 64
NO_RANK = F32(1e30)        # the rank of a row with no bucket left to finish
SUP_Q, OPT_Q = 0.9, 0.1    # worst-case and optimistic demand quantiles
RISK_WINDOW_S = 30.0       # slack under which a feasible deadline is at risk


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to bfloat16 (nearest, ties to even), kept as float32."""
    b = np.ascontiguousarray(x, F32).view(U32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(U32).view(F32)


def three_pass(x: np.ndarray) -> np.ndarray:
    """What a bf16x3 product by an exact 1.0 returns: ``hi + lo``."""
    x = np.asarray(x, F32)
    hi = to_bf16(x)
    return (hi + to_bf16(x - hi)).astype(F32)


# ------------------------------------------------------------------ tables
def unit_service(unit, t_in: float, t_out: float) -> np.ndarray:
    """Per-trial service seconds of one unit: LLM units parallelism *
    (in * t_in + out * t_out), other units their recorded duration."""
    if unit.backend.kind == "llm":
        i = np.asarray(unit.input_len, np.float64)
        o = np.asarray(unit.output_len, np.float64)
        p = np.asarray(unit.parallelism, np.float64)
        n = min(len(i), len(o), len(p))
        return p[:n] * (i[:n] * t_in + o[:n] * t_out) if n \
            else np.ones(1)
    d = np.asarray(unit.duration, np.float64)
    return d if len(d) else np.ones(1)


def build_tables(kb: Dict, t_in: float, t_out: float) -> Dict:
    """``samples (G, U, S)``, ``counts (G, U)``, ``cdf (G, U, U+1)``,
    ``graph`` (name -> index), ``units`` (per graph: unit -> index)."""
    names = sorted(kb)
    per = []
    for name in names:
        g = kb[name]
        units = sorted(g.units)
        idx = {u: i for i, u in enumerate(units)}
        svc = [unit_service(g.units[u], t_in, t_out) for u in units]
        # branch probabilities as float32 steps of a float32 running sum,
        # the exit ("$end") last
        steps = np.zeros((len(units), len(units) + 1), F32)
        for u in units:
            counts = g.units[u].next_counts
            tot = sum(counts.values())
            probs = {k: v / tot for k, v in counts.items()} if tot \
                else {"$end": 1.0}
            for tgt, p in probs.items():
                steps[idx[u], len(units) if tgt == "$end" else idx[tgt]] = p
        cum = np.cumsum(steps, axis=1, dtype=F32)
        per.append((units, idx, svc, cum))
    G = len(names)
    U = max(len(p[0]) for p in per)
    S = max(max(len(s) for s in p[2]) for p in per)
    samples = np.zeros((G, U, S), F32)
    counts = np.ones((G, U), F32)
    cdf = np.zeros((G, U, U + 1), F32)
    cdf[:, :, -1] = 1.0
    for gi, (units, idx, svc, cum) in enumerate(per):
        Ug = len(units)
        for ui, s in enumerate(svc):
            samples[gi, ui, :len(s)] = s.astype(F32)
            counts[gi, ui] = len(s)
        # re-derive the steps from the running sum, move the exit to the
        # shared absorbing column U, and sum again (float32 throughout)
        step = np.diff(np.concatenate([np.zeros((Ug, 1), F32), cum], axis=1),
                       axis=1).astype(F32)
        padded = np.zeros((Ug, U + 1), F32)
        padded[:, :Ug] = step[:, :Ug]
        padded[:, U] = step[:, Ug]
        cdf[gi, :Ug] = np.cumsum(padded, axis=1, dtype=F32)
    return {"samples": samples, "counts": counts, "cdf": cdf,
            "graph": {n: i for i, n in enumerate(names)},
            "units": [p[1] for p in per]}


# -------------------------------------------------------------------- walk
def fmix32(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> U32(16))
    x = x * M1
    x = x ^ (x >> U32(13))
    x = x * M2
    return x ^ (x >> U32(16))


def streams(seed: int, key_ids: np.ndarray, refresh_ids: np.ndarray
            ) -> np.ndarray:
    """Per-(app, refresh) walker stream ids."""
    with np.errstate(over="ignore"):
        s = fmix32(U32(int(seed) & 0xFFFFFFFF)
                   ^ (np.asarray(key_ids).astype(U32) * GOLDEN))
        return fmix32(s ^ (np.asarray(refresh_ids).astype(U32) * M1))


def walk(tables: Dict, graph: np.ndarray, start: np.ndarray,
         executed: np.ndarray, stream: np.ndarray, *, n_walkers: int,
         ov_samples: Optional[np.ndarray] = None,
         ov_counts: Optional[np.ndarray] = None,
         precision: str = "highest") -> np.ndarray:
    """Remaining-service samples ``(A, n_walkers)`` of the counter walk.
    ``ov_samples (A, U, So)`` / ``ov_counts (A, U)`` replace a unit's
    demand samples for one app where the count is positive."""
    samples, counts, cdf = tables["samples"], tables["counts"], tables["cdf"]
    if precision == "high":
        samples, counts, cdf = (three_pass(samples), three_pass(counts),
                                three_pass(cdf))
        if ov_samples is not None:
            ov_samples = three_pass(ov_samples)
    elif precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    A, W = len(graph), n_walkers
    U = cdf.shape[1]
    app = np.repeat(np.arange(A), W)
    gi = np.repeat(np.asarray(graph, np.int64), W)
    cur = np.repeat(np.asarray(start, np.int64), W)
    st = np.repeat(np.asarray(stream, U32), W)
    lane = np.tile(np.arange(W, dtype=U32), A)
    ex = np.repeat(np.asarray(executed, F32), W)
    total = np.zeros(A * W, F32)
    alive = np.arange(A * W)              # walkers not yet absorbed
    with_ov = ov_counts is not None and np.any(ov_counts > 0)
    for s in range(MAX_STEPS):
        if not len(alive):
            break
        a_gi, a_cur, a_app = gi[alive], cur[alive], app[alive]
        with np.errstate(over="ignore"):
            ctr = U32(s) * U32(W) + lane[alive]
            bits = fmix32(st[alive] + ctr * GOLDEN)
        r = (bits >> U32(16)).astype(np.int32).astype(F32) * U16_SCALE
        r2 = (bits & U32(0xFFFF)).astype(np.int32).astype(F32) * U16_SCALE
        n_eff = counts[a_gi, a_cur]
        if with_ov:
            oc = ov_counts[a_app, a_cur].astype(F32)
            n_eff = np.where(oc > 0, oc, n_eff)
        si = np.floor(r * n_eff).astype(np.int64)
        svc = samples[a_gi, a_cur, np.minimum(si, samples.shape[2] - 1)]
        if with_ov:
            So = ov_samples.shape[2]
            svc = np.where(oc > 0,
                           ov_samples[a_app, a_cur, np.minimum(si, So - 1)],
                           svc)
        if s == 0:
            svc = np.maximum(svc - ex[alive], F32(0))
        total[alive] = total[alive] + svc
        nxt = np.sum(r2[:, None] > cdf[a_gi, a_cur], axis=1)
        nxt = np.minimum(nxt, U)
        done = nxt >= U
        cur[alive] = np.where(done, a_cur, nxt)
        alive = alive[~done]
    return total.reshape(A, W)


# --------------------------------------------------------------- histogram
def histogram(total: np.ndarray, n_buckets: int):
    """Floor-binned rows: ``probs (A, nb)``, right ``edges (A, nb)``."""
    total = np.asarray(total, F32)
    A, W = total.shape
    lo = total.min(axis=1)
    hi = total.max(axis=1)
    pad = np.maximum(np.abs(lo) * F32(1e-3), F32(1e-6)).astype(F32)
    hi = np.where(hi <= lo, (lo + pad).astype(F32), hi)
    span = (hi - lo).astype(F32)
    norm = (F32(n_buckets) / span).astype(F32)
    idx = ((total - lo[:, None]) * norm[:, None]).astype(np.int32)
    idx = np.clip(idx, 0, n_buckets - 1)
    cnt = np.zeros((A, n_buckets), np.int64)
    np.add.at(cnt, (np.repeat(np.arange(A), W), idx.ravel()), 1)
    probs = cnt.astype(F32) * F32(1.0 / max(W, 1))
    frac = np.arange(1, n_buckets + 1, dtype=F32) * F32(1.0 / n_buckets)
    edges = (lo[:, None] + np.maximum(span[:, None] * frac[None, :],
                                      F32(0))).astype(F32)
    edges[:, -1] = hi
    return probs, edges


# ------------------------------------------------------------------ triage
def _quantile(srt: np.ndarray, q: float) -> np.ndarray:
    """Row quantile of sorted rows: ``lo + (hi - lo) * frac`` at position
    ``q * (W - 1)``, in float32."""
    pos = q * (srt.shape[1] - 1)
    k = int(np.floor(pos))
    frac = F32(pos - k)
    lo = srt[:, k]
    hi = srt[:, min(k + 1, srt.shape[1] - 1)]
    return (lo + ((hi - lo) * frac).astype(F32)).astype(F32)


def triage(total: np.ndarray):
    """``(sup, opt, mean)`` of each row of walker totals ``(A, W)``: the
    P90, the P10 and the mean, float32."""
    total = np.asarray(total, F32)
    srt = np.sort(total, axis=1)
    return (_quantile(srt, SUP_Q), _quantile(srt, OPT_Q),
            total.mean(axis=1, dtype=F32))


def slacks(sup, opt, attained, deadline, now: float):
    """Seconds left before the deadline once the P90 (``sup``) and the P10
    (``opt``) demand is served: ``deadline - now - max(q - attained, 0)``,
    float64; an application without a deadline (inf) has infinite slack."""
    att = np.asarray(attained, np.float64)
    ddl = np.asarray(deadline, np.float64)
    return tuple(ddl - now - np.maximum(np.asarray(q, np.float64) - att, 0.0)
                 for q in (sup, opt))


def ddl_class(sup, opt, attained, deadline, now: float) -> np.ndarray:
    """§3.3 class of each application: 2 (hopeless) when even the
    optimistic demand misses the deadline, 0 (at risk) when the P90 slack
    is under the risk window, 1 (safe) otherwise."""
    s_sup, s_opt = slacks(sup, opt, attained, deadline, now)
    return np.where(s_opt < 0.0, 2, np.where(s_sup < RISK_WINDOW_S, 0, 1))


# -------------------------------------------------------------------- rank
def _sum_lr(x: np.ndarray) -> np.ndarray:
    acc = x[..., 0].copy()
    for k in range(1, x.shape[-1]):
        acc = (acc + x[..., k]).astype(F32)
    return acc


def gittins(probs: np.ndarray, edges: np.ndarray, attained: np.ndarray
            ) -> np.ndarray:
    """Gittins index of each row's demand histogram at its attained
    service: min over candidate quanta of E[min(X - a, d)] / P(X - a <= d)
    over bucket midpoints past ``a``; a row that outlived its last edge
    ranks at its attained service, one with no midpoint past ``a`` at
    ``NO_RANK``."""
    probs = np.asarray(probs, F32)
    edges = np.asarray(edges, F32)
    a_in = np.asarray(attained, F32)[:, None]
    first = (F32(2) * edges[:, :1] - edges[:, 1:2]).astype(F32)
    left = np.concatenate([first, edges[:, :-1]], axis=1)
    mids = (F32(0.5) * (left + edges)).astype(F32)
    max_edge = edges[:, -1:]
    exhausted = a_in[:, 0] >= max_edge[:, 0]
    a = np.minimum(a_in, (max_edge * F32(1 - 1e-6)).astype(F32))
    alive = mids > a
    p_tail = np.where(alive, probs, F32(0))
    mass = np.maximum(_sum_lr(p_tail), F32(1e-12))
    p_cond = (p_tail / mass[:, None]).astype(F32)
    rem = np.where(alive, (mids - a).astype(F32), F32(0))
    n = edges.shape[1]
    ranks = np.full(len(probs), NO_RANK, F32)
    for j in range(n):
        d = rem[:, j:j + 1]
        e = _sum_lr(np.maximum((np.minimum(rem, d) * p_cond).astype(F32),
                               F32(0)))
        p = _sum_lr(np.where(rem <= d, p_cond, F32(0)))
        ok = (p > F32(1e-12)) & alive[:, j]
        ratio = np.where(ok, (e / np.maximum(p, F32(1e-12))).astype(F32),
                         NO_RANK)
        ranks = np.minimum(ranks, ratio)
    return np.where(exhausted, a_in[:, 0], ranks).astype(F32)


def rows_from_apps(tables: Dict, apps: Sequence[Dict]) -> Dict:
    """Walk inputs in the reference's own layout from application records
    (``app``, ``unit``, ``executed``, ``attained``, ``key_id``,
    ``refresh_id``, ``overrides``: unit name -> demand samples)."""
    graph = np.asarray([tables["graph"][a["app"]] for a in apps], np.int64)
    start = np.asarray([tables["units"][g][a["unit"]]
                        for g, a in zip(graph, apps)], np.int64)
    col = {k: np.asarray([a[k] for a in apps], t) for k, t in
           (("executed", F32), ("attained", F32), ("key_id", np.int64),
            ("refresh_id", np.int64))}
    return dict(col, graph=graph, start=start,
                overrides=[a["overrides"] for a in apps])


def _override_rows(tables: Dict, graph: np.ndarray, overrides: Sequence):
    """``ov_samples (A, U, S)`` / ``ov_counts (A, U)`` of a block of
    override dicts, or ``(None, None)`` when none carries any.  A unit's
    samples are cut to the knowledge base's sample width."""
    if not any(overrides):
        return None, None
    U, S = tables["samples"].shape[1:]
    ovs = np.zeros((len(graph), U, S), F32)
    ovc = np.zeros((len(graph), U), np.int32)
    for i, (g, ov) in enumerate(zip(graph, overrides)):
        for unit, arr in ov.items():
            u = tables["units"][int(g)].get(unit)
            arr = np.asarray(arr, F32)[:S]
            if u is None or not len(arr):
                continue
            ovs[i, u, :len(arr)] = arr
            ovc[i, u] = len(arr)
    return ovs, ovc


def refresh_rows(tables: Dict, rows: Dict, *, seed: int, n_walkers: int,
                 n_buckets: int, precision: str = "highest",
                 block: int = 256, with_triage: bool = False):
    """Walk and histogram the rows in blocks: returns ``probs, edges`` for
    every row of ``rows`` (``graph``, ``start``, ``executed``,
    ``attained``, ``key_id``, ``refresh_id``, and either ``overrides``, a
    dict per row, or ``ov_samples`` / ``ov_counts`` arrays), and with
    ``with_triage`` a third item, ``(sup, opt, mean)`` of every row."""
    n = len(rows["graph"])
    probs = np.zeros((n, n_buckets), F32)
    edges = np.zeros((n, n_buckets), F32)
    tri = np.zeros((3, n), F32)
    st = streams(seed, rows["key_id"], rows["refresh_id"])
    for b in range(0, n, block):
        sl = slice(b, min(b + block, n))
        if "overrides" in rows:
            ovs, ovc = _override_rows(tables, rows["graph"][sl],
                                      rows["overrides"][sl])
        elif rows.get("ov_counts") is not None:
            ovs, ovc = rows["ov_samples"][sl], rows["ov_counts"][sl]
        else:
            ovs = ovc = None
        rem = walk(tables, rows["graph"][sl], rows["start"][sl],
                   rows["executed"][sl], st[sl], n_walkers=n_walkers,
                   ov_samples=ovs, ov_counts=ovc, precision=precision)
        total = (np.asarray(rows["attained"][sl], F32)[:, None]
                 + np.maximum(rem, F32(0))).astype(F32)
        probs[sl], edges[sl] = histogram(total, n_buckets)
        if with_triage:
            tri[:, sl] = triage(total)
    return (probs, edges, tuple(tri)) if with_triage else (probs, edges)
