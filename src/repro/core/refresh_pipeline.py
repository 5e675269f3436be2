"""Device-resident refresh pipeline (§3.3 hot path, Fig. 15).

One jitted dispatch chains the whole bucket-tick estimate refresh —

    MC walk  →  row-wise bucketize  →  Gittins rank  (→ triage quantiles,
                                                      → prewarm triggers)

— over packed PDGraph tables and the persistent slot arena
(:mod:`repro.core.arena`).  Only small per-app results (ranks, triage
scalars, prewarm triggers) ever cross the host boundary; the
``(A, n_walkers)`` sample matrix and the histogram rows stay on device.

Two walker backends:

* ``walker="threefry"`` — the original ``_walk_core`` under vmap with the
  per-(app, refresh) fold_in chain: bit-identical demand samples to the
  composed host path, so device ranks match it to float32 tolerance.
  The equivalence baseline.
* ``walker="pallas"`` — the counter-RNG ``pdgraph_walk`` kernel package
  (Pallas kernel on TPU, bit-identical jnp twin elsewhere): breaks the
  threefry bottleneck and adds phase compaction; distributionally
  equivalent (KS-tested), and the default.

**Delta refresh** (``refresh_ranks_delta``) is the scale path: each tick
gathers only the dirty slots, walks just those rows, scatters their fresh
histogram rows back into the device arena, and re-ranks EVERY occupied slot
in place from the persisted histograms at the current attained service —
one dispatch, sized by the dirty set, not the queue.  The scheduler falls
back to a full re-walk when the dirty fraction crosses its threshold.

**Prewarm retriggering** (delta mode): the dispatch also persists each
walked app's per-unit *arrival histograms* in the arena, and every full
tick re-derives the §3.4 trigger quantiles from them ON DEVICE, conditioned
on the service attained since the walk (``P[arrival > δ]`` survivorship —
the bucketized analogue of the legacy planner's ``tail = s[s > elapsed]``
re-quantile).  Trigger times therefore keep moving between re-walks instead
of freezing at walk time; at δ=0 the conditioned math reduces bit-exactly
to the walk-time trigger.  The multi-device mesh front-end lives in
:mod:`repro.core.refresh_mesh` and runs this same pipeline per shard.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.arena import QueueState
from repro.core.gittins import (N_BUCKETS, gittins_rank_core,
                                gittins_rank_hist, to_histogram_rows_jnp)
from repro.core.pdgraph import ARRIVAL_NEVER, PackedKB, _mc_walk_batch
from repro.core.policies import HOPELESS_Q, SUP_Q
from repro.core.posterior import posterior_tables
from repro.kernels.pdgraph_walk.ops import (pdgraph_walk,
                                            pdgraph_walk_ranked,
                                            walker_streams)
from repro.kernels.pdgraph_walk.quant import quant_tables
from repro.runtime.tracing import span


def _arrival_hists(arr, n_buckets):
    """Per-walker first-arrival times -> per-(app, unit) arrival histograms.

    arr: (A, W, U) cumulative service at each walker's first entry into each
    unit (ARRIVAL_NEVER where never entered).  Returns ``(hist (A, U, nb)
    counts, lo (A, U), span (A, U), n_reach (A, U))`` — the persistable
    sufficient statistics for §3.4 trigger quantiles (same floor binning as
    the rank pipeline's ``to_histogram_rows_jnp``)."""
    A, W, U = arr.shape
    reached = arr < ARRIVAL_NEVER / 2                       # (A, W, U)
    n_reach = reached.sum(axis=1).astype(jnp.float32)       # (A, U)
    t_lo = jnp.where(reached, arr, ARRIVAL_NEVER)
    lo = t_lo.min(axis=1)                                   # (A, U)
    hi = jnp.where(reached, arr, -ARRIVAL_NEVER).max(axis=1)
    span = jnp.maximum(hi - lo, 1e-6)
    idx = ((arr - lo[:, None, :]) * (n_buckets / span)[:, None, :])
    idx = jnp.clip(idx.astype(jnp.int32), 0, n_buckets - 1)
    # one-hot reduce per unit (U is static and small): peak intermediate is
    # (A, W, nb) — same as the rank histogram — instead of the full
    # (A, W, U, nb) cross product, which at benchmark scale (4096 apps x
    # 512 walkers) would be a few-hundred-MB device allocation
    buckets = jnp.arange(n_buckets)
    hist = jnp.stack(
        [((idx[:, :, u, None] == buckets) & reached[:, :, u, None])
         .sum(axis=1) for u in range(U)], axis=1).astype(jnp.float32)
    return hist, lo, span, n_reach


def _triggers_from_hists(hist, lo, span, n_reach, n_walkers, delta,
                         uc, class_warmup, K, stretch):
    """Arrival histograms -> per-(app, backend-class) prewarm triggers,
    conditioned on ``delta`` seconds of service attained since the walk
    (§3.4 generalized to all downstream units; the re-quantile analogue of
    the legacy planner's ``tail = s[s > elapsed]``).

    hist/lo/span/n_reach: (A, U, nb) / (A, U) from :func:`_arrival_hists`
    delta:       (A,) service attained since the histograms were recorded
                 (0 at walk time — the conditioned math then reduces
                 bit-exactly to the unconditioned walk-time trigger)
    uc:          (A, U, Kc) int32 backend-class ids per unit (-1 = none)
    class_warmup:(B,) float32 warm-up seconds per class
    K:           effectiveness knob (traced scalar — one compile serves the
                 whole Fig. 14 K sweep)
    stretch:     (A,) queueing-delay correction: observed wall seconds per
                 service second (1.0 = continuous execution, the §3.4
                 default)

    Per (app, unit): the surviving reach mass is ``n_reach * P[arr > delta]``
    (walkers that would have entered a unit the app demonstrably hasn't
    entered are falsified); where the surviving reach probability >= K the
    trigger quantile is ``Quantile_{arr - delta | arr > delta}(1 - K/p)``
    read off the truncated histogram CDF (linear interpolation inside the
    crossing bucket).  Per (app, class): the earliest ``stretch * quantile -
    warm-up`` over contributing units.  Returns ``(trigger (A, B), reach
    (A, B))`` with ARRIVAL_NEVER marking "do not prewarm"."""
    n_buckets = hist.shape[-1]
    B = class_warmup.shape[0]
    denom = jnp.maximum(n_reach, 1.0)
    cdf = jnp.cumsum(hist, axis=-1) / denom[..., None]      # (A, U, nb)
    width = span / n_buckets

    # survivor mass above delta: interpolated CDF at delta, exactly 0 when
    # delta <= lo so the delta=0 path multiplies/adds only exact values
    pos = (delta[:, None] - lo) / width                     # bucket units
    jb = jnp.clip(pos.astype(jnp.int32), 0, n_buckets - 1)[..., None]
    cdf_jb_prev = jnp.where(
        jb > 0, jnp.take_along_axis(cdf, jnp.maximum(jb - 1, 0), -1),
        0.0)[..., 0]
    p_jb = jnp.take_along_axis(hist, jb, -1)[..., 0] / denom
    frac_d = jnp.clip(pos - jb[..., 0].astype(jnp.float32), 0.0, 1.0)
    cdf_at = jnp.where(delta[:, None] <= lo, 0.0,
                       cdf_jb_prev + p_jb * frac_d)
    surv = jnp.maximum(1.0 - cdf_at, 0.0)

    p_reach = (n_reach * surv) / n_walkers                  # conditioned
    ok = p_reach >= K                                       # coverage gate
    q = jnp.clip(1.0 - K / jnp.maximum(p_reach, 1e-9), 0.0, 1.0)
    # target mass in the ORIGINAL (unconditioned) CDF coordinates
    q_abs = cdf_at + surv * q

    # quantile: first bucket whose CDF reaches q_abs, linearly interpolated
    k = jnp.argmax(cdf >= q_abs[..., None] - 1e-7, axis=-1)  # (A, U)
    kk = k[..., None]
    cdf_prev = jnp.where(
        kk > 0, jnp.take_along_axis(cdf, jnp.maximum(kk - 1, 0), -1),
        0.0)[..., 0]
    p_k = jnp.take_along_axis(hist, kk, -1)[..., 0] / denom
    frac = jnp.clip((q_abs - cdf_prev) / jnp.maximum(p_k, 1e-9), 0.0, 1.0)
    qtile = lo + (k.astype(jnp.float32) + frac) * width     # (A, U)
    # queueing-delay correction: arrival quantiles are in cumulative-service
    # seconds; the observed wall/service stretch converts them to wall time
    # (stretch == 1.0 multiplies bit-exactly — the correction-off path stays
    # bit-identical to the uncorrected pipeline)
    qtile = (qtile - delta[:, None]) * stretch[:, None]

    # scatter-min into backend classes:  trigger(a,b) = min over units of
    # (quantile - warm-up) where unit u needs class b and passes the gate
    cand = qtile[..., None] - class_warmup[jnp.maximum(uc, 0)]
    gate = ok[..., None] & (uc >= 0)
    cls = uc[..., None] == jnp.arange(B)                    # (A, U, Kc, B)
    hit = cls & gate[..., None]
    trigger = jnp.min(jnp.where(hit, cand[..., None], ARRIVAL_NEVER),
                      axis=(1, 2))                          # (A, B)
    reach = jnp.max(jnp.where(hit, p_reach[..., None, None], 0.0),
                    axis=(1, 2))                            # (A, B)
    return trigger, reach


def _walk_total(samples, counts, cum_trans, graph_idx, start, executed,
                attained, key_ids, refresh_ids, base_key, seed,
                ov_samples, ov_counts, valid, *,
                n_walkers, max_steps, walker, impl, with_overrides,
                compact_after, compact_shrink, with_prewarm,
                compact_schedule=None, po_cum=None, po_scale=None):
    """The shared walk section of every pipeline: (A,) queue rows -> TOTAL
    demand samples ``(total (A, W), arr (A, W, U) | None, spill)``.  Pure
    per-row math keyed by per-app RNG streams, so the same rows produce the
    same bits whatever dispatch (full, delta, mesh shard) batches them.

    ``po_cum (A, U, U+1)`` / ``po_scale (A, U)`` switch on posterior-blended
    sampling (:func:`repro.core.posterior.posterior_tables`); ``None`` keeps
    every walker's frozen-prior bits."""
    arr = None
    if walker == "threefry":
        # the composed path's walker verbatim — ONE implementation carries
        # the fold_in chain, so device/composed bit identity cannot drift
        out = _mc_walk_batch(samples, counts, cum_trans,
                             graph_idx, start, executed,
                             base_key, key_ids, refresh_ids,
                             ov_samples, ov_counts, n_walkers, max_steps,
                             track_arrivals=with_prewarm,
                             po_cum=po_cum, po_scale=po_scale)
        rem, arr = out if with_prewarm else (out, None)
        spill = jnp.zeros((), jnp.int32)
    elif walker == "pallas":
        streams = walker_streams(seed, key_ids, refresh_ids)
        out = pdgraph_walk(
            samples, counts, cum_trans, graph_idx, start, executed, streams,
            ov_samples if with_overrides else None,
            ov_counts if with_overrides else None,
            valid=valid, n_walkers=n_walkers, max_steps=max_steps,
            impl=impl, compact_after=compact_after,
            compact_shrink=compact_shrink,
            compact_schedule=compact_schedule,
            track_arrivals=with_prewarm,
            po_cum=po_cum, po_scale=po_scale)
        (rem, arr, spill) = out if with_prewarm else (out[0], None, out[1])
    else:
        raise ValueError(f"unknown walker {walker!r}")
    total = attained[:, None] + jnp.maximum(rem, 0.0)
    return total, arr, spill


def _walk_ranked(samples, counts, cum_trans, graph_idx, start, executed,
                 attained, key_ids, refresh_ids, seed, ov_samples, ov_counts,
                 valid, qsv, qic, *, n_walkers, max_steps, n_buckets, impl,
                 with_overrides, compact_after, compact_shrink, with_prewarm,
                 with_triage, po_cum=None, po_scale=None):
    """The ``rank_in_kernel`` walk section: ONE ``pdgraph_walk_ranked``
    dispatch carries the rows from transition sampling to demand-histogram
    rows, ranks, and arrival statistics — VMEM-resident on the kernel path,
    the quantized multi-stage twin on CPU.  ``qsv``/``qic`` are the lossless
    16-bit step tables (``(1,)`` dummies disable them; shapes are static, so
    the gate is trace-time).  Returns the ``pdgraph_walk_ranked`` dict —
    bit-identical to the :func:`_walk_total` composition."""
    streams = walker_streams(seed, key_ids, refresh_ids)
    return pdgraph_walk_ranked(
        samples, counts, cum_trans, graph_idx, start, executed, streams,
        attained,
        ov_samples if with_overrides else None,
        ov_counts if with_overrides else None,
        valid=valid, n_walkers=n_walkers, max_steps=max_steps,
        n_buckets=n_buckets, impl=impl,
        compact_after=compact_after, compact_shrink=compact_shrink,
        track_arrivals=with_prewarm, with_rank=True, with_total=with_triage,
        po_cum=po_cum, po_scale=po_scale,
        quant=(qsv, qic) if qsv.shape[0] > 1 else None)


def _quantile_rows(x_sorted, q):
    """Row-wise linear-interpolation quantile with COMPILE-STABLE bits.

    ``jnp.quantile`` is numerically fine but its lerp may or may not be
    FMA-contracted depending on the surrounding program (the delta tick and
    the mesh shard program compile separately), drifting the
    result by an ulp between pipelines.  Here the rank indices are static,
    and the optimization barrier between the multiply and the add pins the
    rounding to mul-then-add in every compilation — the sharded/unsharded
    parity contract covers these scalars bit-for-bit."""
    n = x_sorted.shape[1]
    pos = q * (n - 1)
    k = int(np.floor(pos))
    frac = np.float32(pos - k)
    lo = x_sorted[:, k]
    hi = x_sorted[:, min(k + 1, n - 1)]
    return lo + jax.lax.optimization_barrier((hi - lo) * frac)


def _triage_stats(total):
    """On-device §3.3 triage scalars for the composite policies: the same
    (P_sup, P_hopeless, mean) the host ``_demand_stats`` pulls from raw
    samples — computed here before the sample matrix dies on device."""
    srt = jnp.sort(total, axis=1)
    sup = _quantile_rows(srt, SUP_Q)
    opt = _quantile_rows(srt, HOPELESS_Q)
    return sup, opt, total.mean(axis=1)


@partial(jax.jit, static_argnames=("n_walkers", "max_steps", "n_buckets",
                                   "walker", "impl", "with_overrides",
                                   "compact_after", "compact_shrink",
                                   "with_prewarm", "with_retrigger",
                                   "with_triage", "with_posterior",
                                   "branch_strength", "demand_strength",
                                   "rank_in_kernel"))
def _delta_pipeline(samples, counts, cum_trans,        # packed KB tables
                    graph_idx, start, executed, attained,   # (D,) dirty rows
                    key_ids, refresh_ids, base_key, seed,
                    ov_samples, ov_counts, valid, stretch,  # (D, ...) rows
                    slot_idx,                               # (D,) arena slots
                    d_probs, d_edges,                       # (cap, nb) arena
                    attained_all,                           # (cap,)
                    a_hist, a_lo, a_span, a_reach,          # arrival arena
                    gi_all, delta_all, stretch_all,         # (cap,) rows
                    unit_class, class_warmup, prewarm_k,
                    post,                                   # (cap, U, U+3)
                    qsv, qic,                               # quant tables | (1,) dummies
                    *, n_walkers: int, max_steps: int, n_buckets: int,
                    walker: str, impl: Optional[str], with_overrides: bool,
                    compact_after: int, compact_shrink: int,
                    with_prewarm: bool, with_retrigger: bool,
                    with_triage: bool, with_posterior: bool = False,
                    branch_strength: float = 8.0,
                    demand_strength: float = 8.0,
                    rank_in_kernel: bool = False):
    """The delta tick: walk ONLY the gathered dirty rows, scatter their
    fresh histogram rows (demand AND arrival) back into the persistent
    device arena, and re-rank every slot in place from the persisted
    histograms at the current attained service.  ``slot_idx`` padding rows
    carry an out-of-bounds index and are dropped by the scatter.

    With ``with_retrigger`` the same dispatch re-derives the §3.4 prewarm
    triggers for the WHOLE arena from the persisted arrival histograms,
    conditioned on ``delta_all`` (service attained since each slot's last
    walk) — trigger times track elapsed time between re-walks instead of
    freezing at walk time.  Without it (event-path subset refreshes) only
    the walked rows' triggers are computed, at delta=0, exactly as a full
    walk would.

    With ``with_posterior`` each walked row's device posterior row (gathered
    from the arena's ``post`` mirror at ``slot_idx``) is blended with the
    frozen prior into per-row walk tables; rows with zero observations walk
    on the prior bitwise.  ``post`` is a 1-element dummy when off.

    Returns ``(d_probs', d_edges', ranks (cap,), spill, sup, opt, mean,
    a_hist', a_lo', a_span', a_reach', trigger, reach)`` — triage sized by
    the dirty set; trigger/reach sized (cap, B) with retriggering, (D, B)
    without."""
    po_cum = po_scale = None
    if with_posterior:
        # padded dirty rows gather a clamped (garbage) posterior row; their
        # walks are dropped by the out-of-bounds scatter like every other
        # padding-row product
        rows = post[jnp.minimum(slot_idx, post.shape[0] - 1)]
        prior_mean = jnp.sum(samples, axis=-1) / jnp.maximum(
            counts.astype(jnp.float32), 1.0)
        po_cum, po_scale = posterior_tables(
            rows, cum_trans[graph_idx], prior_mean[graph_idx],
            branch_strength=branch_strength,
            demand_strength=demand_strength)
    if rank_in_kernel:
        # one-pass walk → histogram rows (→ arrival stats); the per-row
        # in-kernel ranks are superseded by the arena-wide rank-in-place
        # below (bit-identical for the walked rows — same histogram rows,
        # same attained — and un-walked slots need ranking regardless)
        res = _walk_ranked(
            samples, counts, cum_trans, graph_idx, start, executed,
            attained, key_ids, refresh_ids, seed, ov_samples, ov_counts,
            valid, qsv, qic, n_walkers=n_walkers, max_steps=max_steps,
            n_buckets=n_buckets, impl=impl, with_overrides=with_overrides,
            compact_after=compact_after, compact_shrink=compact_shrink,
            with_prewarm=with_prewarm, with_triage=with_triage,
            po_cum=po_cum, po_scale=po_scale)
        probs, edges, spill, total = (res["probs"], res["edges"],
                                      res["spill"], res["total"])
    else:
        total, arr, spill = _walk_total(
            samples, counts, cum_trans, graph_idx, start, executed, attained,
            key_ids, refresh_ids, base_key, seed, ov_samples, ov_counts,
            valid, n_walkers=n_walkers, max_steps=max_steps, walker=walker,
            impl=impl, with_overrides=with_overrides,
            compact_after=compact_after, compact_shrink=compact_shrink,
            with_prewarm=with_prewarm, po_cum=po_cum, po_scale=po_scale)
        probs, edges = to_histogram_rows_jnp(total, n_buckets)
    d_probs = d_probs.at[slot_idx].set(probs, mode="drop")
    d_edges = d_edges.at[slot_idx].set(edges, mode="drop")
    # rank-in-place: per-row math over the whole arena — bit-identical per
    # row to ranking the (D, nb) rows alone, so delta == full re-walk for
    # the dirty set; holes produce garbage ranks the host never reads
    ranks = gittins_rank_core(d_probs, d_edges, attained_all)
    sup = opt = mean = None
    if with_triage:
        sup, opt, mean = _triage_stats(total)
    trigger = reach = None
    if with_prewarm:
        if rank_in_kernel:
            hist, lo, span, n_reach = (res["a_hist"], res["a_lo"],
                                       res["a_span"], res["a_reach"])
        else:
            hist, lo, span, n_reach = _arrival_hists(arr, n_buckets)
        a_hist = a_hist.at[slot_idx].set(hist, mode="drop")
        a_lo = a_lo.at[slot_idx].set(lo, mode="drop")
        a_span = a_span.at[slot_idx].set(span, mode="drop")
        a_reach = a_reach.at[slot_idx].set(n_reach, mode="drop")
        if with_retrigger:
            trigger, reach = _triggers_from_hists(
                a_hist, a_lo, a_span, a_reach, n_walkers, delta_all,
                unit_class[gi_all], class_warmup, prewarm_k, stretch_all)
        else:
            trigger, reach = _triggers_from_hists(
                hist, lo, span, n_reach, n_walkers,
                jnp.zeros_like(attained), unit_class[graph_idx],
                class_warmup, prewarm_k, stretch)
    return (d_probs, d_edges, ranks, spill, sup, opt, mean,
            a_hist, a_lo, a_span, a_reach, trigger, reach)


@partial(jax.jit, static_argnames=("n_walkers",))
def _rank_retrigger_pipeline(d_probs, d_edges, attained_all,
                             a_hist, a_lo, a_span, a_reach,
                             gi_all, delta_all, stretch_all,
                             unit_class, class_warmup, prewarm_k,
                             *, n_walkers: int):
    """Walk-free tick: rank the whole arena in place AND re-condition every
    prewarm trigger on elapsed service — the empty-dirty-set fast path when
    prewarming is live."""
    ranks = gittins_rank_core(d_probs, d_edges, attained_all)
    trigger, reach = _triggers_from_hists(
        a_hist, a_lo, a_span, a_reach, n_walkers, delta_all,
        unit_class[gi_all], class_warmup, prewarm_k, stretch_all)
    return ranks, trigger, reach


def _prewarm_args(packed, prewarm_table, io=None):
    """The ``PrewarmTable`` constants, resident on the device: uploaded once
    per table object (a KB repack builds a new one) through ``io`` (a
    :class:`_Crossings`; a fresh one counts for no one)."""
    io = io or _Crossings()
    if prewarm_table is not None:
        t = prewarm_table
        return io.resident(("prewarm", id(t)),
                           lambda: (t.unit_class, t.warmup), source=t)
    # 1-class placeholders keep the arg list static-shape friendly
    G, U = packed.samples.shape[0], packed.n_units
    return io.resident(("prewarm", G, U),
                       lambda: (np.full((G, U, 1), -1, np.int32),
                                np.zeros(1, np.float32)))


def _ranked_args(packed: PackedKB, walker: str, impl: Optional[str],
                 rank_in_kernel: Optional[bool]):
    """Resolve the ``rank_in_kernel`` knob (default: on for the pallas
    walker, mirroring ``RefreshConfig``) and build its quantized-step
    operands: the real memoized tables when the CPU twin will run, ``(1,)``
    dummies otherwise (the pipelines gate trace-time by shape)."""
    if rank_in_kernel is None:
        rank_in_kernel = walker == "pallas"
    elif rank_in_kernel and walker != "pallas":
        raise ValueError(
            "rank_in_kernel=True requires walker='pallas' (the "
            f"{walker!r} walker has no fused one-pass program)")
    use_quant = rank_in_kernel and (
        impl == "ref" or (impl is None and jax.default_backend() != "tpu"))
    if use_quant:
        qsv, qic = quant_tables(packed.samples, packed.counts,
                                packed.cum_trans)
    else:
        qsv, qic = _quant_dummies()
    return rank_in_kernel, qsv, qic


@lru_cache(maxsize=1)
def _quant_dummies():
    """Stable (1,) placeholders for the quant-table argument slots — one
    allocation per process, so device placements keyed by buffer identity
    (the mesh's replicated cache, jit donation checks) never churn."""
    return jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.uint8)


def _dispatch_rows(qs: QueueState, slots: np.ndarray, packed: PackedKB,
                   prewarm_table, pad_to: Optional[int] = None, io=None):
    """Host-side marshalling for the delta dispatch: padded
    row gather (host arrays), override-width trim, prewarm constants
    (resident, through ``io``)."""
    gi, start, executed, attained, kid, rid, stretch, ovs, ovc = \
        qs.gather(slots, pad_to=pad_to)
    with_ov = qs.override_apps > 0
    if not with_ov and ovs.shape[2] > 1:
        ovs = ovs[:, :, :1]                  # keep the no-override jit cache
    uc, wt = _prewarm_args(packed, prewarm_table, io)
    return gi, start, executed, attained, kid, rid, stretch, ovs, ovc, \
        with_ov, uc, wt


def _store_results(qs: QueueState, slots: np.ndarray, n_buckets: int,
                   n_classes, sup, opt, mean) -> None:
    """Write one dispatch's per-slot triage scalars into the store's host
    mirrors."""
    qs.ensure_result_rows(n_buckets, n_classes)
    if sup is not None:
        qs.sup[slots] = sup
        qs.opt[slots] = opt
        qs.mean[slots] = mean


@dataclass
class DeltaTick:
    """Results of one delta tick: arena-wide ranks plus the set of slots
    whose estimates were actually re-walked, and the dispatch's
    host->device uploads and device->host reads, with the reads whose host
    copy started at the enqueue."""
    ranks: np.ndarray          # (capacity,) — index by slot id; holes garbage
    spill: int
    walked: np.ndarray         # slot ids re-walked (and scattered) this tick
    h2d: int = 0
    d2h: int = 0
    d2h_async: int = 0


# Device copies of the dispatch constants, most recently used last: key ->
# (source object or None, arrays).  An id-keyed entry holds its source, so
# a recycled id cannot alias a freed table.
_RESIDENT: "OrderedDict[tuple, tuple]" = OrderedDict()
# entries kept before the least recently used is evicted: a few KB
# generations' prewarm tables besides the placeholders and K values in use
_RESIDENT_CAP = 32


class _Crossings:
    """Counts one dispatch's host<->device transfers.  A per-dispatch host
    array goes through ``put``, which hands it back unchanged for the jit
    to upload on its C++ argument path; a constant comes from ``resident``
    (``zeros``, ``scalar``), which uploads it only when the process-wide
    cache misses; the results' host copies start in ``start`` and are
    read back together in ``get``."""

    def __init__(self):
        self.h2d = self.d2h = self.d2h_async = 0

    def put(self, x):
        self.h2d += 1
        return x

    def resident(self, key, host, source=None):
        """Device copies of the host arrays ``host()`` returns, uploaded on
        the first call for ``key`` (and ``source``) and counted there."""
        ent = _RESIDENT.get(key)
        if ent is None or ent[0] is not source:
            ent = (source, tuple(jax.device_put(a) for a in host()))
            self.h2d += len(ent[1])
            _RESIDENT[key] = ent
            if len(_RESIDENT) > _RESIDENT_CAP:
                _RESIDENT.popitem(last=False)
        _RESIDENT.move_to_end(key)
        return ent[1]

    def zeros(self, *shape, dtype=np.float32):
        """A resident zero placeholder for a disabled argument slot."""
        return self.resident(("zeros", shape, np.dtype(dtype)),
                             lambda: (np.zeros(shape, dtype),))[0]

    def scalar(self, value):
        """A resident float32 scalar (the prewarm K)."""
        return self.resident(("scalar", float(value)),
                             lambda: (np.float32(value),))[0]

    def start(self, out):
        """Start every device result's copy to the host now, so the copies
        queue behind the program that makes them instead of each waiting
        for the read before it; counted per array started."""
        for v in out.values():
            if hasattr(v, "copy_to_host_async"):
                v.copy_to_host_async()
                self.d2h_async += 1

    def get(self, out):
        """Every result of ``out`` on the host, in one read; counted per
        array."""
        self.d2h += len(out)
        return jax.device_get(out)


def _retrigger_rows(qs: QueueState, walked: np.ndarray, io: _Crossings):
    """Arena-wide rows for the trigger re-conditioning: graph ids, elapsed
    service since each slot's last walk (0 for the rows walked THIS tick),
    and the stretch EWMA."""
    delta_all = qs.attained - qs.a_att
    if len(walked):
        delta_all[walked] = 0.0
    return io.put(qs.graph_idx), io.put(delta_all), io.put(qs.stretch)


def refresh_ranks_delta(packed: PackedKB, qs: QueueState, base_key, seed,
                        *, walked: np.ndarray,
                        n_walkers: int = 512, max_steps: int = 64,
                        n_buckets: int = N_BUCKETS, walker: str = "pallas",
                        impl: Optional[str] = None,
                        compact_after: int = 16, compact_shrink: int = 4,
                        prewarm_table=None, prewarm_k: float = 0.5,
                        retrigger: bool = True,
                        with_triage: bool = False,
                        posterior=None,
                        rank_in_kernel: Optional[bool] = None,
                        path: Optional[str] = None) -> DeltaTick:
    """One delta tick over the slot store: walk ``walked`` (normally the
    drained dirty set), scatter their histogram rows into the device arena,
    re-rank every slot in place.  With an empty ``walked`` the tick is a
    pure rank-in-place dispatch — no MC walk at all.  Fresh triage scalars
    land in the store's host mirrors for exactly the walked slots.

    ``posterior`` (a :class:`repro.core.posterior.PosteriorConfig`) blends
    each walked row's device posterior row into its walk tables; ``None``
    (the default) leaves every trace and jit cache key untouched.

    With prewarming, ``retrigger=True`` (full ticks) re-conditions EVERY
    slot's trigger rows on the service attained since its last walk —
    the host mirrors are fresh for the whole arena, so the planner covers
    apps that were never re-walked; ``retrigger=False`` (event-path subset
    calls) computes walk-time triggers for just the walked rows, keeping
    per-event cost sized by the event.  Does NOT bump refresh ids; callers
    bump ``walked`` after consuming.

    The dispatch runs in three host spans, ``hermes.<path>.prepare`` (row
    gather and uploads, the enqueue, and the start of every result's copy
    to the host), ``.wait`` (one read of all the results) and ``.consume``
    (the host mirrors), with ``<path>`` as the caller names it (``path``),
    else ``tick`` for ``retrigger`` and ``event`` otherwise; the returned
    tick counts the uploads (``h2d``), the reads (``d2h``) and the reads
    whose copy started at the enqueue (``d2h_async``).  The row arrays
    reach the jit as host arrays; the constants (prewarm tables, K,
    placeholders) stay resident on the device after their first upload."""
    if qs.n_shards != 1:
        raise ValueError("refresh_ranks_delta serves 1-shard arenas; "
                         "mesh-sharded stores go through refresh_ranks_mesh")
    path = path or ("tick" if retrigger else "event")
    io = _Crossings()
    with_pw = prewarm_table is not None
    D = len(walked)
    with span(path + ".prepare"):
        qs.ensure_result_rows(n_buckets,
                              prewarm_table.n_classes if with_pw else None,
                              arrivals=with_pw)
        att_all = io.put(qs.attained)
        if D == 0:
            if with_pw and retrigger:
                uc, wt = _prewarm_args(packed, prewarm_table, io)
                gi_all, delta_all, stretch_all = \
                    _retrigger_rows(qs, walked, io)
                ranks, trigger, reach = _rank_retrigger_pipeline(
                    qs.d_probs, qs.d_edges, att_all,
                    qs.a_hist, qs.a_lo, qs.a_span, qs.a_reach,
                    gi_all, delta_all, stretch_all,
                    uc, wt, io.scalar(prewarm_k), n_walkers=n_walkers)
                out = {"ranks": ranks, "trigger": trigger, "reach": reach}
            else:
                out = {"ranks": gittins_rank_hist(qs.d_probs, qs.d_edges,
                                                  att_all)}
        else:
            gi, start, executed, attained, kid, rid, stretch, ovs, ovc, \
                with_ov, uc, wt = _dispatch_rows(qs, walked, packed,
                                                 prewarm_table, io=io)
            ap = len(gi)
            # padding rows scatter out of bounds -> dropped (never clobber
            # a slot)
            slot_idx = np.concatenate([np.asarray(walked, np.int64),
                                       np.full(ap - D, qs.capacity,
                                               np.int64)])
            if with_pw and retrigger:
                gi_all, delta_all, stretch_all = \
                    _retrigger_rows(qs, walked, io)
            else:
                z = io.zeros(1)
                gi_all, delta_all, stretch_all = \
                    io.zeros(1, dtype=np.int32), z, z
            dummy = io.zeros(1, 1)
            with_po = posterior is not None
            if with_po:
                qs.ensure_posterior_rows()
            post = qs.post if with_po else io.zeros(1, 1, 1)
            rank_in_kernel, qsv, qic = _ranked_args(packed, walker, impl,
                                                    rank_in_kernel)
            (qs.d_probs, qs.d_edges, ranks, spill, sup, opt, mean,
             a_hist, a_lo, a_span, a_reach, trigger, reach) = \
                _delta_pipeline(
                    packed.samples, packed.counts, packed.cum_trans,
                    io.put(gi), io.put(start), io.put(executed),
                    io.put(attained), io.put(kid), io.put(rid),
                    base_key, io.put(np.uint32(int(seed) & 0xFFFFFFFF)),
                    io.put(ovs), io.put(ovc),
                    io.put(np.arange(ap) < D), io.put(stretch),
                    io.put(slot_idx), qs.d_probs, qs.d_edges, att_all,
                    qs.a_hist if with_pw else dummy,
                    qs.a_lo if with_pw else dummy,
                    qs.a_span if with_pw else dummy,
                    qs.a_reach if with_pw else dummy,
                    gi_all, delta_all, stretch_all,
                    uc, wt, io.scalar(prewarm_k), post, qsv, qic,
                    n_walkers=n_walkers, max_steps=max_steps,
                    n_buckets=n_buckets, walker=walker, impl=impl,
                    with_overrides=with_ov, compact_after=compact_after,
                    compact_shrink=compact_shrink, with_prewarm=with_pw,
                    with_retrigger=retrigger, with_triage=with_triage,
                    with_posterior=with_po,
                    branch_strength=(posterior.branch_strength if with_po
                                     else 8.0),
                    demand_strength=(posterior.demand_strength if with_po
                                     else 8.0),
                    rank_in_kernel=rank_in_kernel)
            out = {"ranks": ranks, "spill": spill}
            if with_triage:
                out.update(sup=sup, opt=opt, mean=mean)
            if with_pw:
                qs.a_hist, qs.a_lo, qs.a_span, qs.a_reach = \
                    a_hist, a_lo, a_span, a_reach
                out.update(trigger=trigger, reach=reach)
        io.start(out)
    with span(path + ".wait"):
        got = io.get(out)
    with span(path + ".consume"):
        if D and with_pw:
            qs.a_att[walked] = qs.attained[walked]
        if D:
            _store_results(qs, walked, n_buckets,
                           prewarm_table.n_classes if with_pw else None,
                           *(got[k][:D] if with_triage else None
                             for k in ("sup", "opt", "mean")))
        if "trigger" in got:
            if retrigger:
                qs.trig = np.array(got["trigger"])   # writable, whole arena
                qs.reach = np.array(got["reach"])
            else:
                qs.trig[walked] = got["trigger"][:D]
                qs.reach[walked] = got["reach"][:D]
        return DeltaTick(got["ranks"], int(got["spill"]) if D else 0,
                         walked, io.h2d, io.d2h, io.d2h_async)
