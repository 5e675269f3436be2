"""Public wrapper for the PDGraph counter-RNG walker.

``pdgraph_walk`` runs the whole-queue remaining-service walk over packed
knowledge-base tables and returns the (A, n_walkers) totals as a *device*
array — it is designed to be traced inline into the device refresh
pipeline (`repro.core.refresh_pipeline`) so the sample matrix never crosses
the host boundary.

Implementation dispatch:
  impl="pallas"  the Pallas kernel (compiled on TPU, interpreter elsewhere)
  impl="ref"     the flat-gather jnp twin — bit-identical to the kernel and
                 the fast path on CPU, where interpret-mode Pallas would
                 dominate the tick
  impl=None      auto: "pallas" on TPU backends, "ref" otherwise

Phase compaction: walker absorption is heavily front-loaded (the app suite
retires ~75-85% of walkers within the first few transitions), so after
``compact_after`` steps the surviving walkers are packed into an
``N // compact_shrink``-slot phase-2 state and only those keep stepping.
Compaction is exact — the counter RNG is indexed by (stream, original lane,
global step), so a walker draws the same bits wherever it sits — and the
rare capacity overflow is surfaced as a ``spill`` count (spilled walkers
keep their phase-1 partial totals) instead of silently biasing estimates.

Sharded dispatch: ``pdgraph_walk`` is collective-free per-row math, so the
mesh-sharded refresh (`repro.core.refresh_mesh`) traces it inside a
``shard_map`` body, one instance per arena shard.  RNG streams stay
*shard-local*: ``walker_streams`` keys every walker by the app's own
(key id, refresh id) pair — never by batch position or shard — so a row
draws identical bits whether it is walked alone, in the global batch, or
inside any shard.  ``pad_rows`` is the dispatch-row padding policy for the
sharded path: per-shard dirty counts churn every tick, so it quantizes to
1/8-octave steps (bounded jit-shape churn, pad waste capped at ~23% just
above a power of two and ~12.5% elsewhere) instead of the full
power-of-two rounding (up to ~2x waste) the whole-queue paths use.
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.gittins import (gittins_rank_core, to_histogram_rows_jnp)
from repro.core.pdgraph import ARRIVAL_NEVER, _pow2_ceil
from repro.kernels.pdgraph_walk.kernel import (pdgraph_walk_fused_kernel,
                                               pdgraph_walk_kernel)
from repro.kernels.pdgraph_walk.quant import walk_phase_quant
from repro.kernels.pdgraph_walk.ref import walk_phase_ref, walker_streams  # noqa: F401  (re-export)

# dispatch introspection: which implementation the last pdgraph_walk /
# pdgraph_walk_ranked trace actually took ("pallas" | "ref").  Tests assert
# on it (the Pallas-silent-fallback trap: a requested kernel path must
# either run the kernel or warn) — note jit caching means it reflects the
# last TRACE, so assert right after a fresh-shape call.
LAST_DISPATCH: Optional[str] = None
# ... and whether a kernel dispatch ran through the Pallas interpreter
LAST_INTERPRET: Optional[bool] = None
_FALLBACK_WARNED: set = set()


def _note_dispatch(requested: Optional[str], actual: str, reason: str = "",
                   interpret: Optional[bool] = None):
    global LAST_DISPATCH, LAST_INTERPRET
    LAST_DISPATCH = actual
    LAST_INTERPRET = interpret if actual == "pallas" else None
    if requested == "pallas" and actual != "pallas" \
            and reason not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(reason)
        warnings.warn(
            f"pdgraph_walk: requested impl='pallas' fell back to the jnp "
            f"twin ({reason}); the kernel no longer supports this "
            "configuration — file it against docs/KERNELS.md",
            RuntimeWarning, stacklevel=3)


def pad_rows(n: int, min_rows: int = 1) -> int:
    """Quantized dispatch-row padding for per-shard walk batches.

    Rounds ``n`` up to the next multiple of ``pow2_ceil(n) / 8`` (plain
    power-of-two at or below 64): at most 8 distinct padded sizes per
    octave, so the jit cache stays small under per-tick dirty-count churn,
    while the padding waste stays far under the up-to-2x of pure
    power-of-two rounding (<= q/(2^k+1) ~= 23% just above a power of two,
    ~12.5% elsewhere).  Below 64 rows the multinomial tick-to-tick
    scatter of per-shard dirty counts straddles quanta constantly — there,
    coarse pow2 buckets trade a few idle padding rows (walked dead,
    ``valid=False``) for a stable compiled shape; at large batches the
    fine quanta are the difference between a half-idle and a busy walk
    dispatch."""
    n = max(n, min_rows, 1)
    p = _pow2_ceil(n)
    if n <= 64:
        return p
    q = p // 8
    return ((n + q - 1) // q) * q


def _phase(flat_tables, ov_tables, state, *, step0, n_steps, lanes_per_app,
           impl, interpret, arrivals=None, po_tables=(None, None),
           quant_tables=None):
    """One walk phase via the kernel or its jnp twin (identical bits).

    ``arrivals`` (N, U) switches on first-arrival tracking; both backends
    carry it (the kernel as a (U, L) lane-major block), bit-identically.
    ``ov_tables`` / ``po_tables`` (flat per-app override and posterior
    rows) reach both backends: the twin gathers them, the kernel consumes
    them as app-blocked one-hot operands (app-major phases only — the
    dispatcher disables compaction for kernel walks with per-app tables).
    ``quant_tables`` (qsv, icdf) switch the twin to the lossless 16-bit
    quantized step (``quant.walk_phase_quant``, bit-identical; ineligible
    with overrides — the caller gates)."""
    fsamples, fcounts, fcum = flat_tables
    fov_s, fov_c = ov_tables
    fpo_cum, fpo_scale = po_tables
    cur, total, done, gi, app, stream, lane, executed = state
    if impl == "pallas":
        return pdgraph_walk_kernel(
            fsamples.T, fcounts, fcum.T, cur, gi, stream, lane, executed,
            total, done, arrivals, fov_s, fov_c, fpo_scale, fpo_cum,
            step0=step0, n_steps=n_steps, lanes_per_app=lanes_per_app,
            interpret=interpret)
    if quant_tables is not None and fov_s is None:
        qsv, qic = quant_tables
        return walk_phase_quant(qsv, qic, cur, total, done, gi, app,
                                stream, lane, executed,
                                n_units=fcum.shape[1] - 1,
                                step0=step0, n_steps=n_steps,
                                lanes_per_app=lanes_per_app,
                                arrivals=arrivals,
                                fpo_cum=fpo_cum, fpo_scale=fpo_scale)
    return walk_phase_ref(fsamples, fcounts, fcum, fov_s, fov_c,
                          cur, total, done, gi, app, stream, lane, executed,
                          step0=step0, n_steps=n_steps,
                          lanes_per_app=lanes_per_app, arrivals=arrivals,
                          fpo_cum=fpo_cum, fpo_scale=fpo_scale)


def pdgraph_walk(samples: jnp.ndarray,        # (G, U, S)
                 counts: jnp.ndarray,         # (G, U)
                 cum_trans: jnp.ndarray,      # (G, U, U+1)
                 graph_idx: jnp.ndarray,      # (A,)
                 start: jnp.ndarray,          # (A,)
                 executed: jnp.ndarray,       # (A,)
                 streams: jnp.ndarray,        # (A,) uint32
                 ov_samples: Optional[jnp.ndarray] = None,   # (A, U, So)
                 ov_counts: Optional[jnp.ndarray] = None,    # (A, U)
                 *, valid: Optional[jnp.ndarray] = None,     # (A,) bool
                 n_walkers: int = 512, max_steps: int = 64,
                 impl: Optional[str] = None, interpret: Optional[bool] = None,
                 compact_after: int = 16, compact_shrink: int = 4,
                 compact_schedule: Optional[Tuple[Tuple[int, int], ...]] = None,
                 track_arrivals: bool = False,
                 po_cum: Optional[jnp.ndarray] = None,       # (A, U, U+1)
                 po_scale: Optional[jnp.ndarray] = None,     # (A, U)
                 quant: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None
                 ) -> Tuple[jnp.ndarray, ...]:
    """Remaining-service totals for A apps: ``((A, n_walkers), spill)``.

    Pure jnp — safe to call inside an outer jit.  ``streams`` come from
    ``walker_streams(seed, key_ids, refresh_ids)``.  ``valid`` marks real
    queue rows: padding rows start their walkers absorbed, so they neither
    occupy phase-2 compaction capacity nor inflate the spill count.

    ``compact_schedule`` generalizes the single (compact_after,
    compact_shrink) compaction into a multi-stage one: a tuple of
    ``(step, shrink)`` stages, ascending in both, each packing the
    survivors into an ``N // shrink``-slot state at ``step`` (shrink is a
    divisor of the ORIGINAL lane count).  Absorption keeps decaying after
    the first compaction — the app suite leaves ~6% of lanes alive at step
    16 and ~2% at step 32, so a second stage halves the remaining-phase
    cost at a >3x capacity margin (the mesh-sharded refresh's default).
    Compaction is exact, so ANY schedule returns bit-identical totals as
    long as nothing spills; stages that would violate monotonicity, exceed
    ``max_steps``, or drop capacity under 128 lanes disable themselves,
    exactly like the legacy gate.  When None, the schedule is the classic
    ``((compact_after, compact_shrink),)``.

    ``track_arrivals`` additionally returns per-walker first-arrival times
    into every unit — ``((A, W), (A, W, U), spill)`` — feeding the fused
    prewarm planner.  Both backends carry the arrival state (the kernel as a
    (U, L) lane-major block), so the TPU path keeps kernel speed with
    prewarm tracking on; the counter-RNG draws don't depend on the extra
    carry, so totals are bit-identical either way.

    ``po_cum (A, U, U+1)`` / ``po_scale (A, U)`` switch on posterior
    sampling (online PDGraph learning, ``repro.core.posterior``).  Both
    backends consume them — and the override tables — bit-identically: the
    twin as flat gathers, the kernel as app-blocked one-hot operands.
    Blocked per-app tables require app-aligned lane rows, which only hold
    before compaction — kernel walks with either therefore run
    single-phase (compaction is exact, so the bits cannot differ; only the
    spill count, pinned at 0, and the step cost on absorbed lanes do).

    ``quant`` — precomputed ``(qsv, icdf)`` lossless 16-bit step tables
    (``quant.quant_tables``) for the jnp twin; ignored on the kernel path
    and ineligible with overrides (the per-phase gate falls back to the
    reference step).  Bit-identical either way.
    """
    requested = impl
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _note_dispatch(requested, impl, interpret=interpret)
    if impl == "pallas" and (po_cum is not None or ov_samples is not None):
        compact_schedule = ()     # app-blocked tables need phase-1 lanes
    A = graph_idx.shape[0]
    G, U, S = samples.shape
    N = A * n_walkers
    W = n_walkers
    flat_tables = (samples.reshape(G * U, S),
                   counts.reshape(G * U).astype(jnp.float32),
                   cum_trans.reshape(G * U, U + 1))
    with_ov = ov_samples is not None
    ov_tables = ((ov_samples.reshape(A * U, -1),
                  ov_counts.reshape(A * U).astype(jnp.float32))
                 if with_ov else (None, None))
    po_tables = ((po_cum.reshape(A * U, U + 1),
                  po_scale.reshape(A * U).astype(jnp.float32))
                 if po_cum is not None else (None, None))

    rep = lambda a, dt: jnp.repeat(jnp.asarray(a, dt), W)  # noqa: E731
    gi = rep(graph_idx, jnp.int32)
    app = jnp.repeat(jnp.arange(A, dtype=jnp.int32), W)
    stream = rep(streams, jnp.uint32)
    lane = jnp.tile(jnp.arange(W, dtype=jnp.uint32), A)
    done0 = (jnp.zeros((N,), bool) if valid is None
             else jnp.repeat(~jnp.asarray(valid, bool), W))
    state = (rep(start, jnp.int32),                       # cur
             jnp.zeros((N,), jnp.float32),                # total
             done0,
             gi, app, stream, lane,
             rep(executed, jnp.float32))

    # validate the schedule trace-time: stages ascending in step AND shrink,
    # inside (0, max_steps), capacity >= 128 lanes; offending stages disable
    # themselves (the legacy single-stage gate, per stage)
    if compact_schedule is None:
        compact_schedule = ((compact_after, compact_shrink),)
    stages = []
    prev_step, prev_shrink = 0, 1
    for step, shrink in compact_schedule:
        if step <= prev_step or step >= max_steps:
            continue
        if shrink <= prev_shrink or N // shrink < 128:
            continue
        stages.append((step, shrink))
        prev_step, prev_shrink = step, shrink

    arr = (jnp.full((N, U), ARRIVAL_NEVER, jnp.float32)
           if track_arrivals else None)
    cur, total, done, gi_c, app_c, stream_c, lane_c, executed_c = state
    spill = jnp.zeros((), jnp.int32)
    unwind = []                      # (totals, arrivals, keep) per level
    seg_start = 0
    for step_b, shrink in stages + [(max_steps, None)]:
        out = _phase(flat_tables, ov_tables,
                     (cur, total, done, gi_c, app_c, stream_c, lane_c,
                      executed_c),
                     step0=seg_start, n_steps=step_b - seg_start,
                     lanes_per_app=W, impl=impl, interpret=interpret,
                     arrivals=arr, po_tables=po_tables,
                     quant_tables=quant if impl == "ref" else None)
        if track_arrivals:
            cur, total, done, arr = out
        else:
            cur, total, done = out
        if shrink is None:
            break
        C = N // shrink
        order = jnp.argsort(done.astype(jnp.int32))       # stable: alive first
        keep = order[:C]
        spill += jnp.maximum(jnp.sum(~done) - C, 0).astype(jnp.int32)
        unwind.append((total, arr, keep))
        cur, done = cur[keep], done[keep]
        gi_c, app_c = gi_c[keep], app_c[keep]
        stream_c, lane_c = stream_c[keep], lane_c[keep]
        total = total[keep]
        if track_arrivals:
            arr = arr[keep]
        executed_c = None                                 # step 0 only
        seg_start = step_b
    # unwind the compaction levels: each level's kept lanes take the deeper
    # totals; spilled lanes keep their partial (pre-compaction) totals
    for total_prev, arr_prev, keep in reversed(unwind):
        total = total_prev.at[keep].set(total)
        if track_arrivals:
            arr = arr_prev.at[keep].set(arr)
    if track_arrivals:
        return total.reshape(A, W), arr.reshape(A, W, U), spill
    return total.reshape(A, W), spill


def walk_schedule(compact_after: int, compact_shrink: int,
                  n_lanes: int) -> Tuple[Tuple[int, int], ...]:
    """Lane-count-gated multi-stage compaction schedule (static at trace
    time) — the mesh's measured-absorption schedule, shared with the
    fused-rank twin dispatch.

    Walker absorption keeps decaying long after the single PR-4 compaction
    point — measured on the app suite at benchmark scale: ~9.4% of lanes
    alive at step 12 (vs 25% capacity), ~2.2% at 28 (vs 6.25%), ~0.7% at 44
    (vs 1.6%) — so at large batches three stages cut the tail-phase walk
    cost ~40% while every stage keeps a >2x *average* capacity margin.
    Small batches don't average: one slow-absorbing row is a triple-digit
    slice of a small stage capacity, so under 16k lanes the schedule stays
    the classic conservative single stage.  Compaction is exact, so the
    schedule changes no bits unless a stage spills (surfaced per call).  A
    caller who tuned the single-stage knobs away from the (16, 4) default
    keeps their stage, extended with one 4x-shrink tail stage; a caller who
    DISABLED compaction (shrink <= 1 or a degenerate step — the legacy
    gate's off switches) keeps it disabled, never silently re-enabled."""
    if compact_shrink <= 1 or compact_after <= 0:
        return ((compact_after, compact_shrink),)      # off stays off
    if (compact_after, compact_shrink) != (16, 4):
        return ((compact_after, compact_shrink),
                (compact_after * 2, compact_shrink * 4))
    if n_lanes >= 16384:
        return ((12, 4), (28, 16), (44, 64))
    return ((compact_after, compact_shrink),)


def pdgraph_walk_ranked(samples: jnp.ndarray,     # (G, U, S)
                        counts: jnp.ndarray,      # (G, U)
                        cum_trans: jnp.ndarray,   # (G, U, U+1)
                        graph_idx: jnp.ndarray,   # (A,)
                        start: jnp.ndarray,       # (A,)
                        executed: jnp.ndarray,    # (A,)
                        streams: jnp.ndarray,     # (A,) uint32
                        attained: jnp.ndarray,    # (A,)
                        ov_samples: Optional[jnp.ndarray] = None,
                        ov_counts: Optional[jnp.ndarray] = None,
                        *, valid: Optional[jnp.ndarray] = None,
                        n_walkers: int = 512, max_steps: int = 64,
                        n_buckets: int = 10,
                        impl: Optional[str] = None,
                        interpret: Optional[bool] = None,
                        compact_after: int = 16, compact_shrink: int = 4,
                        track_arrivals: bool = False,
                        with_rank: bool = True, with_total: bool = False,
                        po_cum: Optional[jnp.ndarray] = None,
                        po_scale: Optional[jnp.ndarray] = None,
                        quant: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None):
    """One-pass walk → demand-histogram rows → Gittins ranks (→ arrival
    histogram rows): the VMEM-resident refresh.

    Returns a dict with keys ``probs (A, nb)``, ``edges (A, nb)``,
    ``ranks (A,)`` (``None`` unless ``with_rank``), ``total (A, W)``
    (``None`` unless ``with_total`` — the triage escape hatch; it
    reintroduces the (A, W) write-back), ``spill``, and with
    ``track_arrivals`` the arrival sufficient statistics ``a_hist
    (A, U, nb)``, ``a_lo / a_span / a_reach (A, U)`` — bit-identical to
    composing :func:`pdgraph_walk` with ``to_histogram_rows_jnp`` /
    ``gittins_rank_core`` / ``refresh_pipeline._arrival_hists`` on
    ``attained[:, None] + max(rem, 0)``.

    Dispatch:

    * ``impl="pallas"`` — ONE ``pallas_call`` (``pdgraph_walk_fused_kernel``)
      carries each app-aligned walker block from transition sampling to the
      per-app rows; the ``(A, W)`` totals and ``(A, W, U)`` arrival tensor
      never leave VMEM (unless ``with_total``).  Single-phase by
      construction: compaction is exact, so the resident pass returns the
      same bits a compacted multi-phase walk would (spill pinned 0).
    * ``impl="ref"`` — the CPU twin: the lossless quantized step tables
      (``quant``, see ``quant.py``) where eligible (no overrides), the
      lane-gated multi-stage compaction schedule (``walk_schedule``), then
      the oracle composition — bit-identical to the kernel, and to the
      ``rank_in_kernel=False`` pipeline composition.
    """
    requested = impl
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    A = graph_idx.shape[0]
    G, U, S = samples.shape
    W = n_walkers
    N = A * W
    attained = jnp.asarray(attained, jnp.float32)

    if impl == "pallas":
        _note_dispatch(requested, "pallas", interpret=interpret)
        with_ov = ov_samples is not None
        rep = lambda a, dt: jnp.repeat(jnp.asarray(a, dt), W)  # noqa: E731
        done0 = (jnp.zeros((N,), bool) if valid is None
                 else jnp.repeat(~jnp.asarray(valid, bool), W))
        total_o, probs, edges, ranks, arrstats = pdgraph_walk_fused_kernel(
            samples.reshape(G * U, S).T,
            counts.reshape(G * U).astype(jnp.float32),
            cum_trans.reshape(G * U, U + 1).T, attained,
            rep(start, jnp.int32), rep(graph_idx, jnp.int32),
            rep(streams, jnp.uint32),
            jnp.tile(jnp.arange(W, dtype=jnp.uint32), A),
            rep(executed, jnp.float32), done0,
            ov_samples.reshape(A * U, -1) if with_ov else None,
            ov_counts.reshape(A * U) if with_ov else None,
            po_scale.reshape(A * U) if po_cum is not None else None,
            po_cum.reshape(A * U, U + 1) if po_cum is not None else None,
            n_steps=max_steps, lanes_per_app=W, n_buckets=n_buckets,
            arrival_never=ARRIVAL_NEVER, with_arrivals=track_arrivals,
            with_rank=with_rank, with_total=with_total,
            interpret=interpret)
        out = {"probs": probs, "edges": edges, "ranks": ranks,
               "total": None, "spill": jnp.zeros((), jnp.int32)}
        if with_total:
            rem = total_o.reshape(A, W)
            out["total"] = attained[:, None] + jnp.maximum(rem, 0.0)
        if track_arrivals:
            st = arrstats                          # (A, U, nb + 3)
            out.update(a_hist=st[..., :n_buckets], a_lo=st[..., n_buckets],
                       a_span=st[..., n_buckets + 1],
                       a_reach=st[..., n_buckets + 2])
        return out

    # CPU twin: quantized multi-stage walk + the oracle reduction — the
    # rank_in_kernel pipelines call this, so the quantized step and the
    # aggressive schedule stay gated behind the knob (the legacy
    # composition keeps its exact cost profile as the A/B reference)
    if quant is not None and ov_samples is not None:
        quant = None                       # overrides change n_eff per app
    out = pdgraph_walk(
        samples, counts, cum_trans, graph_idx, start, executed, streams,
        ov_samples, ov_counts, valid=valid, n_walkers=n_walkers,
        max_steps=max_steps, impl="ref", interpret=interpret,
        compact_schedule=walk_schedule(compact_after, compact_shrink, N),
        track_arrivals=track_arrivals, po_cum=po_cum, po_scale=po_scale,
        quant=quant)
    if track_arrivals:
        rem, arr, spill = out
    else:
        (rem, spill), arr = out, None
    total = attained[:, None] + jnp.maximum(rem, 0.0)
    res = {"total": total if with_total else None, "spill": spill,
           "probs": None, "edges": None, "ranks": None}
    if with_rank:
        probs, edges = to_histogram_rows_jnp(total, n_buckets)
        res.update(probs=probs, edges=edges,
                   ranks=gittins_rank_core(probs, edges, attained))
    if track_arrivals:
        from repro.core.refresh_pipeline import _arrival_hists
        a_hist, a_lo, a_span, a_reach = _arrival_hists(arr, n_buckets)
        res.update(a_hist=a_hist, a_lo=a_lo, a_span=a_span, a_reach=a_reach)
    return res


@partial(jax.jit, static_argnames=("n_walkers", "max_steps", "impl",
                                   "interpret", "compact_after",
                                   "compact_shrink", "compact_schedule",
                                   "track_arrivals"))
def pdgraph_walk_jit(samples, counts, cum_trans, graph_idx, start, executed,
                     streams, ov_samples=None, ov_counts=None, *,
                     n_walkers: int = 512, max_steps: int = 64,
                     impl: Optional[str] = None,
                     interpret: Optional[bool] = None,
                     compact_after: int = 16, compact_shrink: int = 4,
                     compact_schedule=None,
                     track_arrivals: bool = False):
    """Jitted standalone entry point (tests / direct benchmarking)."""
    return pdgraph_walk(samples, counts, cum_trans, graph_idx, start,
                        executed, streams, ov_samples, ov_counts,
                        n_walkers=n_walkers, max_steps=max_steps, impl=impl,
                        interpret=interpret, compact_after=compact_after,
                        compact_shrink=compact_shrink,
                        compact_schedule=compact_schedule,
                        track_arrivals=track_arrivals)
