"""Mesh-sharded refresh backbone: the delta pipeline across N devices.

``RefreshMesh`` partitions the slot arena over a 1-D device mesh
(``("shard",)``): shard *s* owns every slot with ``slot % n_shards == s``
(see :mod:`repro.core.arena` for why residue placement, and for the
shard-major device-row layout that makes each shard's rows one contiguous
block).  Each tick is ONE jitted ``shard_map`` dispatch in which every
shard, entirely locally,

1. walks ITS dirty rows (shard-local RNG streams — keyed by the apps'
   own (key id, refresh id) pairs, so placement cannot change a single
   drawn bit),
2. scatters the fresh demand + arrival histogram rows into ITS arena
   block,
3. re-ranks ITS stale rows (walked ∪ progressed) from the persisted
   histograms at the current attained service, and
4. (prewarming) re-conditions ITS trigger rows on elapsed service.

No collective runs on the default tick: the only cross-shard
"communication" is the host gather of the small per-tick results — the
stale-row ranks, the walked rows' triage scalars, and the trigger rows the
merged ``PrewarmPlan`` is built from.  Sample matrices, arrival tensors
and histogram arenas stay sharded on their devices for their whole life.
The one deliberate exception is the **lane-balanced** tick
(``lane_balance``): when per-shard dirty counts diverge past the
threshold, walked rows are assigned round-robin and each shard's packed
result rows ride ONE ``all_gather`` back to their owner shards — a few
KB of histogram rows traded against the straggler gap of a skewed dirty
set.

Because every stage is per-row math and the RNG is position-independent,
the mesh tick is **bit-identical** to the single-shard delta path for the
same slot placement — at any shard count, under any dirty-set partition
(pinned by ``tests/test_refresh_mesh.py``).

Unlike the single-arena path (which re-ranks the whole arena each tick —
cheap at one device, pure waste times N at mesh scale), the mesh tick
ranks only the *stale* rows and serves everyone else from the arena's
host rank mirror; with churn at a few percent per tick, per-tick host
traffic shrinks from O(capacity) to O(churn).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.arena import QueueState
from repro.core.gittins import N_BUCKETS, gittins_rank_core, \
    to_histogram_rows_jnp
from repro.core.pdgraph import PackedKB
from repro.core.posterior import posterior_tables
from repro.core.refresh_pipeline import (_arrival_hists, _ranked_args,
                                         _triage_stats, _triggers_from_hists,
                                         _walk_ranked, _walk_total)
from repro.kernels.pdgraph_walk.ops import pad_rows


class RefreshMesh:
    """A 1-D device mesh the slot arena is partitioned over.

    ``n_shards`` must be a power of two and at most the number of visible
    devices (CI forces host devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  One shard per
    device; ``n_shards=1`` is the degenerate mesh used to A/B the sharded
    pipeline against the single-arena path on one device."""

    def __init__(self, n_shards: int = 1, devices=None):
        if n_shards < 1 or n_shards & (n_shards - 1):
            raise ValueError(f"n_shards must be a power of two, got "
                             f"{n_shards}")
        devices = list(jax.devices() if devices is None else devices)
        if n_shards > len(devices):
            raise ValueError(
                f"RefreshMesh wants {n_shards} shards but only "
                f"{len(devices)} devices are visible (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={n_shards} for a "
                f"CPU mesh)")
        self.n_shards = n_shards
        self.mesh = Mesh(np.asarray(devices[:n_shards]), ("shard",))
        self._rep: dict = {}     # id -> (source ref, replicated placement)

    # id-keyed replicated entries kept before the oldest are evicted: a few
    # KB generations' worth — online refinement retunes graphs and repacks
    # the tables, and without eviction every superseded table set would stay
    # pinned (host array + one replica per device) for the mesh's lifetime
    _REP_CAP = 32

    def replicated(self, arr):
        """Per-mesh cache of fully-replicated placements for slow-changing
        constants (packed KB tables, prewarm tables, the base key).  Without
        this every tick re-broadcasts each constant to all shards — at 8
        devices that is hundreds of buffer puts per dispatch, more host time
        than the walk itself."""
        key = id(arr)
        ent = self._rep.get(key)
        if ent is None or ent[0] is not arr:
            ent = (arr, jax.device_put(arr, NamedSharding(self.mesh, P())))
            self._rep[key] = ent
            self._evict()
        return ent[1]

    def _evict(self) -> None:
        """Drop the oldest id-keyed entries past _REP_CAP (insertion order).
        String-keyed placeholders ("zeros" rows) are bounded by construction
        and exempt — they are shared across KB generations."""
        idk = [k for k in self._rep if not isinstance(k, str)
               and not (isinstance(k, tuple) and isinstance(k[0], str)
                        and k[0] == "zeros")]
        for k in idk[:max(len(idk) - self._REP_CAP, 0)]:
            del self._rep[k]

    def prewarm_constants(self, packed, prewarm_table):
        """Replicated (unit_class, warmup) — the real tables when prewarming,
        the packed-KB-shaped placeholders otherwise (cached either way)."""
        if prewarm_table is not None:
            return (self.replicated(prewarm_table.unit_class),
                    self.replicated(prewarm_table.warmup))
        key = ("pw_placeholder", id(packed))
        ent = self._rep.get(key)
        if ent is None or ent[0] is not packed:
            from repro.core.refresh_pipeline import _prewarm_args
            uc, wt = _prewarm_args(packed, None)
            rep = NamedSharding(self.mesh, P())
            ent = (packed, (jax.device_put(uc, rep),
                            jax.device_put(wt, rep)))
            self._rep[key] = ent
            self._evict()
        return ent[1]

    def zeros_rows(self, key: str, width, dtype) -> jnp.ndarray:
        """Cached row-sharded zero placeholders for the disabled-feature
        argument slots (one element — or ``width`` trailing ones — per
        shard; a tuple width adds several trailing dims), so feature-off
        ticks upload nothing for them."""
        ent = self._rep.get(("zeros", key))
        if ent is None:
            shape = ((self.n_shards,) if width == 0 else
                     (self.n_shards, *width) if isinstance(width, tuple)
                     else (self.n_shards, width))
            arr = jax.device_put(jnp.zeros(shape, dtype),
                                 self.row_sharding(len(shape)))
            ent = (None, arr)
            self._rep[("zeros", key)] = ent
        return ent[1]

    def row_sharding(self, ndim: int) -> NamedSharding:
        """Rows (leading axis) split across shards, trailing dims whole."""
        return NamedSharding(self.mesh, P("shard", *([None] * (ndim - 1))))

    def place(self, arr):
        """Commit a device-arena array to its shard-major row sharding
        (no-op when already placed)."""
        want = self.row_sharding(arr.ndim)
        if getattr(arr, "sharding", None) == want:
            return arr
        return jax.device_put(arr, want)

    def place_state(self, qs: QueueState) -> None:
        """(Re)commit the store's device rows after allocation or growth."""
        for name in ("d_probs", "d_edges", "a_hist", "a_lo", "a_span",
                     "a_reach", "post"):
            a = getattr(qs, name)
            if a is not None:
                setattr(qs, name, self.place(a))


@dataclass
class MeshTick:
    """Results of one mesh tick.  ``ranks`` aligns with ``ranked`` (the
    stale slots actually re-ranked this tick); every other per-slot result
    lands in the store's host mirrors (``rank``/``sup``/``trig``/…)."""
    ranks: np.ndarray          # (R,) — row-aligned with `ranked`
    spill: int
    walked: np.ndarray         # slot ids re-walked this tick
    ranked: np.ndarray         # slot ids re-ranked this tick
    balanced: bool = False     # walker lanes were redistributed this tick


def _mesh_schedule(compact_after: int, compact_shrink: int,
                   n_lanes: int) -> Tuple[Tuple[int, int], ...]:
    """Per-shard multi-stage compaction schedule, sized by the shard's lane
    count (static at trace time).

    Walker absorption keeps decaying long after the single PR-4 compaction
    point — measured on the app suite at benchmark scale: ~9.4% of lanes
    alive at step 12 (vs 25% capacity), ~2.2% at 28 (vs 6.25%), ~0.7% at 44
    (vs 1.6%) — so at large batches three stages cut the tail-phase walk
    cost ~40% while every stage keeps a >2x *average* capacity margin.
    Small per-shard batches (a few dirty rows x walkers) don't average:
    one slow-absorbing row is a triple-digit slice of a small stage
    capacity, so under 16k lanes the schedule stays the classic
    conservative single stage.  Compaction is exact, so the schedule
    changes no bits unless a stage spills (surfaced per shard).  A caller
    who tuned the single-stage knobs away from the (16, 4) default keeps
    their stage, extended with one 4x-shrink tail stage; a caller who
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


# bitcast-carrier column layout (host packs, shard_fn unpacks; int32 columns
# travel as raw float32 bit patterns — transfers and bitcasts are bit-exact)
_COL_GI, _COL_START, _COL_KID, _COL_RID, _COL_SCAT = range(5)
_COL_EXEC, _COL_ATT, _COL_STRETCH, _COL_RANK_ROW, _COL_RANK_ATT = range(5, 10)
_COL_OWNER = 10        # owner shard (slot % n) — read by balanced ticks only
_N_COLS = 11


@lru_cache(maxsize=None)
def _mesh_exec(mesh: Mesh, seed: int, n_walkers: int, max_steps: int,
               n_buckets: int, walker: str, impl: Optional[str],
               with_overrides: bool, compact_after: int, compact_shrink: int,
               with_prewarm: bool, with_retrigger: bool, with_triage: bool,
               with_posterior: bool = False, branch_strength: float = 8.0,
               demand_strength: float = 8.0, rank_in_kernel: bool = False,
               balanced: bool = False):
    """Build (and cache per mesh + static config) the jitted shard_map tick.

    ALL per-tick row state travels in ONE packed ``(n, P, _N_COLS + U)``
    float32 carrier (int32 columns bitcast to raw float32 patterns): at 8
    shards every separate argument costs one buffer put per device per
    tick, so an unpacked argument list — not the walk — would dominate
    host-side dispatch time.  Slow-changing constants (KB tables, prewarm
    tables, base key, quant tables) arrive pre-replicated through
    :meth:`RefreshMesh.replicated`; the arena arrays are committed to their
    row sharding and enter with zero per-tick transfer.

    ``rank_in_kernel`` swaps the walk + bucketize section for ONE
    :func:`_walk_ranked` dispatch per shard (the VMEM-resident program on
    the kernel path; the quantized multi-stage twin on CPU) — bit-identical
    rows.  ``balanced`` is the walker-lane-balancing program: the host
    assigned walked rows round-robin (so per-shard walk cost is even
    regardless of residue skew), and each shard's packed result rows ride
    ONE ``all_gather`` back so every owner scatters exactly its own rows —
    the single collective the module docstring's "no collective" contract
    carves out, traded against the dirty-imbalance straggler gap."""

    def shard_fn(samples, counts, cum_trans,            # replicated KB
                 carrier,               # (1, P, _N_COLS+U) packed row state
                 ovs,                   # (1, P, U, So)
                 d_probs, d_edges,      # (cap_s, nb) — the shard's arena rows
                 a_hist, a_lo, a_span, a_reach,         # (cap_s, ...)
                 post,                                  # (cap_s, U, U+3)
                 gi_rows, delta_rows, stretch_rows,     # (cap_s,)
                 base_key, uc, wt, prewarm_k,           # replicated
                 qsv, qic):             # replicated quant tables | dummies
        # NOTE two block conventions: stacked (n, ...) per-tick batches keep
        # a leading length-1 mesh axis ([0] below); arena arrays enter in
        # their native (cap, …) shard-major layout, so their blocks are the
        # shard's own rows directly (no host reshape, no cross-device copy).
        # Walk rows and rank rows pad INDEPENDENTLY: the carrier is as wide
        # as the larger set, and the walk section reads only its own
        # ``Dw``-row prefix (= the override table's row count) — a balanced
        # tick's whole point is that Dw shrinks to ceil(|walked| / n) even
        # when one shard owns (and must rank) every dirty row.
        c = carrier[0]
        Dw = ovs.shape[1]                     # walk-row pad (<= carrier)
        cw = c[:Dw]
        as_i32 = lambda a, col: jax.lax.bitcast_convert_type(  # noqa: E731
            a[:, col], jnp.int32)
        gi, start, kid, rid, scat = (as_i32(cw, i) for i in range(5))
        executed = cw[:, _COL_EXEC]
        attained = cw[:, _COL_ATT]
        stretch = cw[:, _COL_STRETCH]
        rank_rows = as_i32(c, _COL_RANK_ROW)[None]
        rank_att = c[:, _COL_RANK_ATT][None]
        ovc = jax.lax.bitcast_convert_type(cw[:, _N_COLS:], jnp.int32)[None]
        cap_s = d_probs.shape[0]
        valid = scat < cap_s                  # padding rows carry scat=cap_s
        po_cum = po_scale = None
        if with_posterior:
            # the shard's own arena block holds its slots' posterior rows;
            # the gather + blend is the delta pipeline's math verbatim, and
            # the rows hold host-scattered values identical at any shard
            # count — so sharded == 1-shard bit-for-bit here too.  Padding
            # rows clamp to a garbage row; their walks are dropped.
            rows_p = post[jnp.minimum(scat, post.shape[0] - 1)]
            prior_mean = jnp.sum(samples, axis=-1) / jnp.maximum(
                counts.astype(jnp.float32), 1.0)
            po_cum, po_scale = posterior_tables(
                rows_p, cum_trans[gi], prior_mean[gi],
                branch_strength=branch_strength,
                demand_strength=demand_strength)
        if rank_in_kernel:
            # one-pass walk → histogram rows (→ arrival stats); the per-row
            # in-kernel ranks are unused here — the mesh ranks the stale
            # set from the arena below — but cost a fraction of the walk
            res = _walk_ranked(
                samples, counts, cum_trans, gi, start, executed, attained,
                kid, rid, np.uint32(seed), ovs[0], ovc[0], valid, qsv, qic,
                n_walkers=n_walkers, max_steps=max_steps,
                n_buckets=n_buckets, impl=impl,
                with_overrides=with_overrides, compact_after=compact_after,
                compact_shrink=compact_shrink, with_prewarm=with_prewarm,
                with_triage=with_triage, po_cum=po_cum, po_scale=po_scale)
            probs, edges, spill = res["probs"], res["edges"], res["spill"]
            total = res["total"]               # None unless with_triage
        else:
            total, arr, spill = _walk_total(
                samples, counts, cum_trans, gi, start, executed,
                attained, kid, rid, base_key, np.uint32(seed), ovs[0],
                ovc[0], valid, n_walkers=n_walkers, max_steps=max_steps,
                walker=walker, impl=impl, with_overrides=with_overrides,
                compact_after=compact_after, compact_shrink=compact_shrink,
                with_prewarm=with_prewarm,
                compact_schedule=_mesh_schedule(compact_after,
                                                compact_shrink,
                                                Dw * n_walkers),
                po_cum=po_cum, po_scale=po_scale)
            probs, edges = to_histogram_rows_jnp(total, n_buckets)
        hist = lo = span = n_reach = None
        if with_prewarm:
            if rank_in_kernel:
                hist, lo, span, n_reach = (res["a_hist"], res["a_lo"],
                                           res["a_span"], res["a_reach"])
            else:
                hist, lo, span, n_reach = _arrival_hists(arr, n_buckets)
        ah, al, asp, ar = a_hist, a_lo, a_span, a_reach
        if balanced:
            # walker lanes were host-assigned round-robin, so this shard
            # walked rows it does not own: pack every result row with its
            # owner + owner-local index (raw bit-pattern columns), ONE
            # all-gather, then scatter exactly the rows owned here (every
            # other row — and padding, whose index is already cap_s — maps
            # out of bounds and drops)
            Dp = probs.shape[0]
            meta = jnp.stack([cw[:, _COL_OWNER], cw[:, _COL_SCAT]], axis=1)
            parts = [probs, edges, meta]
            if with_prewarm:
                parts += [hist.reshape(Dp, -1), lo, span,
                          n_reach]
            packed_rows = jnp.concatenate(parts, axis=1)
            g = jax.lax.all_gather(packed_rows, "shard")
            g = g.reshape(-1, packed_rows.shape[1])       # (n*Dp, K)
            nb = n_buckets
            owner = jax.lax.bitcast_convert_type(g[:, 2 * nb], jnp.int32)
            gscat = jax.lax.bitcast_convert_type(g[:, 2 * nb + 1],
                                                 jnp.int32)
            mine = owner == jax.lax.axis_index("shard")
            idx = jnp.where(mine, gscat, cap_s)
            dp = d_probs.at[idx].set(g[:, :nb], mode="drop")
            de = d_edges.at[idx].set(g[:, nb:2 * nb], mode="drop")
            if with_prewarm:
                U = lo.shape[1]
                off = 2 * nb + 2
                ah = ah.at[idx].set(
                    g[:, off:off + U * nb].reshape(-1, U, nb), mode="drop")
                off += U * nb
                al = al.at[idx].set(g[:, off:off + U], mode="drop")
                asp = asp.at[idx].set(g[:, off + U:off + 2 * U],
                                      mode="drop")
                ar = ar.at[idx].set(g[:, off + 2 * U:off + 3 * U],
                                    mode="drop")
        else:
            dp = d_probs.at[scat].set(probs, mode="drop")
            de = d_edges.at[scat].set(edges, mode="drop")
            if with_prewarm:
                ah = ah.at[scat].set(hist, mode="drop")
                al = al.at[scat].set(lo, mode="drop")
                asp = asp.at[scat].set(span, mode="drop")
                ar = ar.at[scat].set(n_reach, mode="drop")
        # rank ONLY the stale rows, gathered from the shard's own arena
        # block (row-wise math: bit-identical to ranking them in place)
        rr = jnp.minimum(rank_rows[0], cap_s - 1)
        ranks = gittins_rank_core(dp[rr], de[rr], rank_att[0])
        if with_triage:
            sup, opt, mean = _triage_stats(total)
        else:
            sup = opt = mean = jnp.zeros((1,), jnp.float32)
        trigger = reach = jnp.zeros((1, 1), jnp.float32)
        if with_prewarm:
            if with_retrigger:
                # (cap_s, B): arena-shaped, like dp/ah — no leading axis
                trigger, reach = _triggers_from_hists(
                    ah, al, asp, ar, n_walkers, delta_rows,
                    uc[gi_rows], wt, prewarm_k, stretch_rows)
            else:
                tw, rw = _triggers_from_hists(
                    hist, lo, span, n_reach, n_walkers,
                    jnp.zeros_like(attained), uc[gi], wt, prewarm_k,
                    stretch)
                trigger, reach = tw[None], rw[None]     # (1, Dp, B)
        exp = lambda x: x[None]                                # noqa: E731
        return (dp, de, exp(ranks), spill.reshape(1),
                exp(sup), exp(opt), exp(mean),
                ah, al, asp, ar,
                trigger, reach)

    rows = P("shard")
    rep = P()
    in_specs = (rep, rep, rep,                     # KB tables
                rows, rows,                        # carrier / ovs
                rows, rows,                        # d_probs / d_edges
                rows, rows, rows, rows,            # arrival arena
                rows,                              # posterior arena
                rows, rows, rows,                  # gi/delta/stretch rows
                rep, rep, rep, rep,                # base_key/uc/wt/K
                rep, rep)                          # quant tables
    out_specs = (rows,) * 13
    return jax.jit(jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _partition(slots: np.ndarray, n: int, pad: int
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Split ascending ``slots`` by shard residue into an (n, pad) matrix
    of global slot ids (-1 padding).  Returns (matrix, by_shard, counts)
    where ``by_shard`` is ``slots`` reordered shard-major (ascending within
    each shard) — the row-major order of the matrix's valid entries."""
    sh = slots % n
    order = np.argsort(sh, kind="stable")      # slots already ascending
    by_shard = slots[order]
    counts = np.bincount(sh, minlength=n)
    mat = np.full((n, pad), -1, np.int64)
    offs = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(len(slots)) - offs[sh[order]]
    mat[sh[order], pos] = by_shard
    return mat, by_shard, counts


def _partition_rr(slots: np.ndarray, n: int, pad: int
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Round-robin (lane-balanced) partition: shard ``s`` WALKS
    ``slots[s::n]`` — per-shard counts differ by at most one whatever the
    residue skew, so no shard straggles.  Same return contract as
    :func:`_partition`; the walking shard is generally not the owner, so
    the balanced tick routes result rows back through the in-dispatch
    all-gather.  RNG streams are keyed by each app's own (key id, refresh
    id), never by placement — the redistributed walk draws identical
    bits."""
    mat = np.full((n, pad), -1, np.int64)
    counts = np.zeros(n, np.int64)
    for s in range(n):
        rows = slots[s::n]
        mat[s, :len(rows)] = rows
        counts[s] = len(rows)
    by_shard = (np.concatenate([slots[s::n] for s in range(n)])
                if len(slots) else slots)
    return mat, by_shard, counts


def refresh_ranks_mesh(packed: PackedKB, qs: QueueState, base_key, seed,
                       *, mesh: RefreshMesh, walked: np.ndarray,
                       ranked: Optional[np.ndarray] = None,
                       n_walkers: int = 512, max_steps: int = 64,
                       n_buckets: int = N_BUCKETS, walker: str = "pallas",
                       impl: Optional[str] = None,
                       compact_after: int = 16, compact_shrink: int = 4,
                       prewarm_table=None, prewarm_k: float = 0.5,
                       retrigger: bool = True, host_work=None,
                       with_triage: bool = False,
                       posterior=None,
                       rank_in_kernel: Optional[bool] = None,
                       lane_balance: Optional[float] = None) -> MeshTick:
    """One mesh tick: walk ``walked`` (shard-partitioned), scatter into the
    sharded arena, re-rank ``ranked`` (default: the walked set), gather the
    small results.  Bit-identical per slot to ``refresh_ranks_delta`` over
    the same sets on one shard.  Does NOT bump refresh ids — but
    ``host_work`` (if given) runs between the async dispatch and the
    result sync, so callers can overlap their per-tick bookkeeping with
    the device walk instead of serializing after it.

    ``posterior`` (a :class:`repro.core.posterior.PosteriorConfig`) blends
    each walked slot's device posterior row (the shard's own arena block)
    into its walk tables — the delta path's blend verbatim, so sharded
    posterior ticks stay bit-identical to 1-shard ones.

    ``rank_in_kernel`` (default: on for ``walker="pallas"``) runs each
    shard's walk + bucketize as ONE ``pdgraph_walk_ranked`` dispatch.
    ``lane_balance`` enables walker-lane balancing: when the per-shard
    dirty counts diverge past ``max > (1 + lane_balance) * mean``, walked
    rows are assigned round-robin and result rows ride one in-dispatch
    all-gather back to their owner shards (disabled while ``posterior`` is
    active — the posterior arena rows are owner-local)."""
    n = mesh.n_shards
    if qs.capacity % n or qs.n_shards != n:
        raise ValueError(f"store is laid out for {qs.n_shards} shards, "
                         f"mesh has {n}")
    with_pw = prewarm_table is not None
    with_po = posterior is not None
    qs.ensure_result_rows(n_buckets,
                          prewarm_table.n_classes if with_pw else None,
                          arrivals=with_pw)
    if with_po:
        qs.ensure_posterior_rows()
    mesh.place_state(qs)
    cap, cap_s = qs.capacity, qs.shard_capacity
    walked = np.asarray(walked, np.int64)
    ranked = walked if ranked is None else np.asarray(ranked, np.int64)

    wcounts = np.bincount(walked % n, minlength=n)
    rcounts = np.bincount(ranked % n, minlength=n)
    # walker-lane balancing: past the divergence threshold, walked rows are
    # assigned round-robin instead of by residue (posterior rows live in
    # the owner's arena block, so posterior ticks stay shard-local)
    balanced = (lane_balance is not None and n > 1 and not with_po
                and len(walked) > 0
                and wcounts.max() > (1.0 + lane_balance)
                * max(len(walked) / n, 1.0))
    wmax = (int(np.ceil(len(walked) / n)) if balanced
            else int(wcounts.max()) if len(walked) else 1)
    # walk rows and rank rows pad INDEPENDENTLY inside one carrier (still a
    # single buffer put per shard per tick): the walk section of the
    # dispatch reads only the first Pw rows, so a balanced tick walks
    # pad(|walked| / n) lanes per shard even though the skewed rows' OWNER
    # shard still ranks all of them from its arena — one shared width would
    # hand every shard's walk the rank set's padding and erase the whole
    # lane-balancing gain
    Pw = pad_rows(max(wmax, 1))
    Pr = pad_rows(max(int(rcounts.max()) if len(ranked) else 1, 1))
    Pp = max(Pw, Pr)                     # carrier width
    wmat, w_by_shard, _ = (_partition_rr if balanced else _partition)(
        walked, n, Pw)
    rmat, r_by_shard, _ = _partition(ranked, n, Pr)

    wvalid = wmat >= 0
    widx = np.where(wvalid, wmat, 0)
    scat = np.where(wvalid, wmat // n, cap_s)        # OOB pad -> dropped
    rvalid = rmat >= 0
    rank_rows = np.where(rvalid, rmat // n, cap_s)   # clamped in-body
    rank_att = qs.attained[np.where(rvalid, rmat, 0)]

    # ONE packed float32 carrier holds every per-row input (int32 columns as
    # raw bit patterns); at 8 shards each extra argument is 8 buffer puts
    # per tick, which would cost more host time than the walk itself
    U = qs.n_units
    carrier = np.empty((n, Pp, _N_COLS + U), np.float32)
    ci = carrier.view(np.int32)
    # walk columns live in the first Pw rows (all the dispatch reads);
    # rank columns in the first Pr.  Pad regions of the rank columns get
    # clamp-safe defaults — their ranks are computed and discarded
    ci[:, :Pw, _COL_GI] = qs.graph_idx[widx]
    ci[:, :Pw, _COL_START] = qs.start[widx]
    ci[:, :Pw, _COL_KID] = qs.key_id[widx]
    ci[:, :Pw, _COL_RID] = qs.refresh_id[widx]
    ci[:, :Pw, _COL_SCAT] = scat
    carrier[:, :Pw, _COL_EXEC] = qs.executed[widx]
    carrier[:, :Pw, _COL_ATT] = qs.attained[widx]
    carrier[:, :Pw, _COL_STRETCH] = qs.stretch[widx]
    ci[:, :, _COL_RANK_ROW] = cap_s
    ci[:, :Pr, _COL_RANK_ROW] = rank_rows
    carrier[:, :, _COL_RANK_ATT] = 0.0
    carrier[:, :Pr, _COL_RANK_ATT] = rank_att
    ci[:, :Pw, _COL_OWNER] = np.where(wvalid, wmat % n, 0)
    ci[:, :Pw, _N_COLS:] = qs.ov_counts[widx]

    with_ov = qs.override_apps > 0
    ovs = qs.ov_samples[widx]
    if not with_ov and ovs.shape[-1] > 1:
        ovs = ovs[..., :1]                 # keep the no-override jit cache
    uc, wt = mesh.prewarm_constants(packed, prewarm_table)
    if with_pw and retrigger:
        # arena-row-ordered (cap,) vectors: shard s's block is its own rows
        row_slots = qs.row_slots()
        delta_all = qs.attained - qs.a_att
        if len(walked):
            delta_all[walked] = 0.0
        gi_rows = qs.graph_idx[row_slots]
        delta_rows = delta_all[row_slots]
        stretch_rows = qs.stretch[row_slots]
    else:
        gi_rows = mesh.zeros_rows("gi", 0, jnp.int32)
        delta_rows = mesh.zeros_rows("f32", 0, jnp.float32)
        stretch_rows = mesh.zeros_rows("f32", 0, jnp.float32)
    dummy = mesh.zeros_rows("dummy2d", 1, jnp.float32)
    dummy3 = mesh.zeros_rows("dummy3d", (1, 1), jnp.float32)

    rank_in_kernel, qsv, qic = _ranked_args(packed, walker, impl,
                                            rank_in_kernel)
    fn = _mesh_exec(mesh.mesh, int(seed) & 0xFFFFFFFF, n_walkers, max_steps,
                    n_buckets, walker, impl, with_ov, compact_after,
                    compact_shrink, with_pw, retrigger and with_pw,
                    with_triage, with_po,
                    posterior.branch_strength if with_po else 8.0,
                    posterior.demand_strength if with_po else 8.0,
                    rank_in_kernel, balanced)
    (dp, de, ranks, spill, sup, opt, mean, ah, al, asp, ar, trigger,
     reach) = fn(
        mesh.replicated(packed.samples), mesh.replicated(packed.counts),
        mesh.replicated(packed.cum_trans),
        carrier, ovs,
        qs.d_probs, qs.d_edges,
        qs.a_hist if with_pw else dummy,
        qs.a_lo if with_pw else dummy,
        qs.a_span if with_pw else dummy,
        qs.a_reach if with_pw else dummy,
        qs.post if with_po else dummy3,
        gi_rows, delta_rows, stretch_rows,
        mesh.replicated(base_key), uc, wt,
        np.float32(prewarm_k),
        mesh.replicated(qsv), mesh.replicated(qic))
    if host_work is not None:
        host_work()                # overlaps the asynchronous dispatch

    qs.d_probs = dp
    qs.d_edges = de
    if with_pw:
        qs.a_hist, qs.a_lo, qs.a_span, qs.a_reach = ah, al, asp, ar
        qs.a_att[walked] = qs.attained[walked]

    # ranks: row-major valid entries align with the shard-major slot order
    # (the dispatch ranks the full carrier width; only the Pr prefix is real)
    rank_vals = np.asarray(ranks)[:, :Pr][rvalid]
    qs.rank[r_by_shard] = rank_vals
    if with_triage and len(walked):
        qs.sup[w_by_shard] = np.asarray(sup)[wvalid]
        qs.opt[w_by_shard] = np.asarray(opt)[wvalid]
        qs.mean[w_by_shard] = np.asarray(mean)[wvalid]
    if with_pw:
        if retrigger:
            # (cap, B) in device-row order -> slot order
            rows = qs.device_rows(np.arange(cap, dtype=np.int64))
            qs.trig = np.asarray(trigger)[rows]
            qs.reach = np.asarray(reach)[rows]
        elif len(walked):
            B = trigger.shape[-1]
            qs.trig[w_by_shard] = np.asarray(trigger).reshape(-1, B)[
                wvalid.ravel()]
            qs.reach[w_by_shard] = np.asarray(reach).reshape(-1, B)[
                wvalid.ravel()]
    return MeshTick(qs.rank[ranked], int(np.asarray(spill).sum()),
                    walked, ranked, balanced)
