"""Slot arena: the persistent per-application store behind the fused refresh.

``QueueState`` is a fixed-capacity power-of-two arena of per-app rows;
capacity grows by doubling and every live application keeps ONE slot id for
its whole lifetime (``admit`` pops a host free-list, ``retire`` pushes back —
holes are masked, never compacted away, so device-resident result rows stay
slot-aligned across membership churn).  Host input rows are mutated in place
O(1) per scheduler event; ``mark_dirty`` accumulates the slots whose PDGraph
position changed (admission, unit transition, refinement override) for the
next delta walk.

**Shard placement** (the mesh-sharded refresh backbone): with ``n_shards``
> 1 the arena is partitioned across a device mesh.  Placement is by residue —
``shard_of(slot) = slot % n_shards`` — so a slot's shard is a pure function
of its id and survives capacity doubling (a contiguous range per shard could
not: doubling would have to renumber every slot past the first range).  Each
shard owns its own free-list and dirty set, and its rows sit contiguously in
the *device* arena via the shard-major row layout

    device_row(slot) = (slot % n_shards) * (capacity // n_shards)
                       + slot // n_shards

(the identity map when ``n_shards == 1``), which is exactly the layout a
``NamedSharding(mesh, P("shard"))`` over rows partitions without any
resharding traffic.  Admission balances shards by free-slot count, so churn
cannot strand one device with the whole queue.

Result rows:

* ``d_probs`` / ``d_edges`` — (cap, n_buckets) demand-histogram rows, DEVICE
  resident (shard-major order); written only by dispatch scatters, read by
  rank-in-place.
* ``a_hist`` / ``a_lo`` / ``a_span`` / ``a_reach`` — per-(app, unit) arrival
  histograms, DEVICE resident (delta mode with prewarming): persisted so
  trigger quantiles can be re-conditioned on elapsed service each tick
  without re-walking (``a_att`` is the host mirror of attained-at-walk).
* ``post`` — (cap, U, U+3) conjugate-posterior sufficient-statistic rows
  (Dirichlet branch counts + Gamma demand sum/count, see
  ``repro.core.posterior``), DEVICE resident (online learning only):
  refreshed by one scatter per tick right before the slots are walked, read
  by the posterior-sampling walk, remapped across grow/repack epochs like
  every other device row.
* ``rank`` — (cap,) host mirror of the last device-computed Gittins rank
  per slot (the mesh path serves unchanged slots from this cache).
* ``sup`` / ``opt`` / ``mean`` — (cap,) triage scalars, host mirrors for
  the composite policies.
* ``trig`` / ``reach`` — (cap, B) prewarm rows, host mirrors the batched
  planner reads (``PrewarmPlan.from_store``).

**Repack**: the arena never shrinks within an epoch (grow-only, holes
masked).  ``repack()`` rebuilds it at the smallest fitting capacity —
slot ids change ONLY across this explicit epoch boundary, so hosts must
call it at a tick boundary when no slot id is held anywhere outside the
store (``repack_epoch`` counts the boundaries; every host mirror and the
device rows are remapped in place, no re-walk needed).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax.numpy as jnp

from repro.core.pdgraph import ARRIVAL_NEVER, PackedKB, _pow2_ceil


class QueueState:
    """Persistent per-application slot store (see module docstring)."""

    def __init__(self, packed: PackedKB, capacity: int = 64,
                 n_shards: int = 1):
        if n_shards < 1 or n_shards & (n_shards - 1):
            raise ValueError(f"n_shards must be a power of two, got {n_shards}")
        self.n_shards = n_shards
        self.n_units = packed.n_units
        self.max_samples = packed.n_samples
        cap = max(_pow2_ceil(capacity), n_shards, 1)
        self.graph_idx = np.zeros(cap, np.int32)
        self.start = np.zeros(cap, np.int32)
        self.executed = np.zeros(cap, np.float32)
        self.attained = np.zeros(cap, np.float32)
        self.key_id = np.zeros(cap, np.int32)
        self.refresh_id = np.zeros(cap, np.int32)
        self.deadline = np.full(cap, np.inf, np.float32)
        self.stretch = np.ones(cap, np.float32)
        self.ov_samples = np.zeros((cap, self.n_units, 1), np.float32)
        self.ov_counts = np.zeros((cap, self.n_units), np.int32)
        self.ids: List[Optional[str]] = [None] * cap
        self.slot: Dict[str, int] = {}
        self._occ = np.zeros(cap, bool)
        self._frees: List[List[int]] = [
            list(range(cap - self.n_shards + s, s - 1, -self.n_shards))
            for s in range(self.n_shards)]
        self.live = 0
        self._dirty: List[set] = [set() for _ in range(self.n_shards)]
        self.rank_dirty: set = set()   # attained moved since last rank write
        self.override_apps = 0       # apps with >= 1 active override row
        self.kb_token = None         # packed-KB version tag (rebuild guard)
        self.repack_epoch = 0        # slot ids are stable within one epoch
        # result rows (allocated lazily, once n_buckets / n_classes known)
        self._nb: Optional[int] = None
        self.d_probs = None          # (cap, nb) jnp — device resident
        self.d_edges = None
        self.rank = np.zeros(cap, np.float32)
        self.sup = np.zeros(cap, np.float32)
        self.opt = np.zeros(cap, np.float32)
        self.mean = np.zeros(cap, np.float32)
        self.trig: Optional[np.ndarray] = None    # (cap, B)
        self.reach: Optional[np.ndarray] = None
        # persisted arrival state (delta-mode prewarm retriggering)
        self.a_hist = None           # (cap, U, nb) jnp — device resident
        self.a_lo = None             # (cap, U) jnp
        self.a_span = None           # (cap, U) jnp
        self.a_reach = None          # (cap, U) jnp
        self.a_att: Optional[np.ndarray] = None   # (cap,) attained at walk
        # conjugate-posterior rows (online PDGraph learning; None = frozen
        # prior, every pre-posterior code path bit-identical)
        self.post = None             # (cap, U, U+3) jnp — device resident

    def __len__(self) -> int:
        return self.live

    @property
    def capacity(self) -> int:
        return self.graph_idx.shape[0]

    @property
    def shard_capacity(self) -> int:
        return self.capacity // self.n_shards

    def occupied(self) -> np.ndarray:
        """Slot ids of all live applications, ascending."""
        return np.nonzero(self._occ)[0]

    # ------------------------------------------------------------- placement
    def shard_of(self, slot: int) -> int:
        return slot % self.n_shards

    def device_rows(self, slots: np.ndarray) -> np.ndarray:
        """Shard-major device-arena row of each slot (identity at 1 shard)."""
        s = np.asarray(slots, np.int64)
        return (s % self.n_shards) * self.shard_capacity + s // self.n_shards

    def row_slots(self) -> np.ndarray:
        """Inverse layout map: the slot id stored at each device row."""
        rows = np.arange(self.capacity, dtype=np.int64)
        return (rows % self.shard_capacity) * self.n_shards \
            + rows // self.shard_capacity

    # ------------------------------------------------------------- dirty set
    @property
    def dirty(self) -> set:
        """Union view of the per-shard dirty sets (read-only: a fresh set)."""
        out: set = set()
        for d in self._dirty:
            out |= d
        return out

    def _add_dirty(self, slot: int) -> None:
        self._dirty[slot % self.n_shards].add(slot)

    def mark_dirty(self, app_id: str) -> None:
        i = self.slot.get(app_id)
        if i is not None:
            self._add_dirty(i)

    def dirty_in(self, slots) -> set:
        """Dirty slots among ``slots`` (any iterable of slot ids)."""
        return {s for s in slots if s in self._dirty[s % self.n_shards]}

    def clear_dirty(self, slots) -> None:
        for s in slots:
            self._dirty[int(s) % self.n_shards].discard(int(s))

    def take_dirty(self) -> np.ndarray:
        """Drain the dirty set (ascending slot ids).  The caller decides
        whether to walk exactly these or fall back to the full occupied
        set when the dirty fraction makes gather/scatter a bad trade."""
        out: List[int] = []
        for d in self._dirty:
            out.extend(d)
            d.clear()
        return np.asarray(sorted(out), np.int64)

    def take_rank_dirty(self, within: Optional[set] = None) -> set:
        """Drain the rank-stale set (slots whose attained moved since their
        rank mirror was written).  ``within`` restricts the drain to a slot
        subset — event-path calls must not steal other apps' pending marks."""
        if within is None:
            out, self.rank_dirty = self.rank_dirty, set()
            return out
        out = self.rank_dirty & within
        self.rank_dirty -= out
        return out

    # ------------------------------------------------------------- capacity
    _ROWS = ("graph_idx", "start", "executed", "attained", "key_id",
             "refresh_id", "deadline", "stretch", "ov_samples", "ov_counts",
             "rank", "sup", "opt", "mean")

    @property
    def _free(self) -> List[int]:
        """Flat view of the per-shard free-lists (diagnostics/tests)."""
        return [s for f in self._frees for s in f]

    def _free_count(self) -> int:
        return sum(len(f) for f in self._frees)

    def _grow(self) -> None:
        old = self.capacity
        extra = ("trig", "reach") if self.trig is not None else ()
        extra += ("a_att",) if self.a_att is not None else ()
        for name in self._ROWS + extra:
            a = getattr(self, name)
            b = np.zeros((old * 2,) + a.shape[1:], a.dtype)
            b[:old] = a
            setattr(self, name, b)
        self.deadline[old:] = np.inf
        self.stretch[old:] = 1.0
        if self.trig is not None:
            self.trig[old:] = ARRIVAL_NEVER
        self.ids.extend([None] * old)
        self._occ = np.concatenate([self._occ, np.zeros(old, bool)])
        for s in range(self.n_shards):
            self._frees[s].extend(
                range(old * 2 - self.n_shards + s, old - 1, -self.n_shards))
        for name in ("d_probs", "d_edges", "a_hist", "a_lo", "a_span",
                     "a_reach", "post"):
            a = getattr(self, name)
            if a is None:
                continue
            # shard-major layout: each shard's block grows in place, so old
            # rows keep their device row *within* the shard and slot ids are
            # untouched (for 1 shard this is a plain concat).  The host rows
            # above have already doubled, so the pre-grow shard width is
            # old // n, not self.shard_capacity
            n, cs = self.n_shards, old // self.n_shards
            blocks = a.reshape((n, cs) + a.shape[1:])
            pad = jnp.zeros((n, cs) + a.shape[1:], a.dtype)
            setattr(self, name,
                    jnp.concatenate([blocks, pad], axis=1)
                    .reshape((old * 2,) + a.shape[1:]))

    def _grow_override_width(self, width: int) -> None:
        width = min(_pow2_ceil(width), self.max_samples)
        if width <= self.ov_samples.shape[2]:
            return
        b = np.zeros(self.ov_samples.shape[:2] + (width,), np.float32)
        b[:, :, :self.ov_samples.shape[2]] = self.ov_samples
        self.ov_samples = b

    def ensure_result_rows(self, n_buckets: int,
                           n_classes: Optional[int] = None,
                           arrivals: bool = False) -> None:
        """Allocate (or re-shape) the persisted result rows."""
        cap = self.capacity
        if self._nb != n_buckets or self.d_probs is None:
            self._nb = n_buckets
            self.d_probs = jnp.zeros((cap, n_buckets), jnp.float32)
            self.d_edges = jnp.zeros((cap, n_buckets), jnp.float32)
            self.a_hist = None      # bucket count changed: arrival rows too
        if n_classes is not None and (
                self.trig is None or self.trig.shape[1] != n_classes):
            self.trig = np.full((cap, n_classes), ARRIVAL_NEVER, np.float32)
            self.reach = np.zeros((cap, n_classes), np.float32)
        if arrivals and self.a_hist is None:
            U = self.n_units
            self.a_hist = jnp.zeros((cap, U, n_buckets), jnp.float32)
            self.a_lo = jnp.zeros((cap, U), jnp.float32)
            self.a_span = jnp.full((cap, U), 1e-6, jnp.float32)
            self.a_reach = jnp.zeros((cap, U), jnp.float32)
            self.a_att = np.zeros(cap, np.float32)

    def ensure_posterior_rows(self) -> None:
        """Allocate the device-resident conjugate-posterior rows (online
        learning only — never allocated when ``posterior=None``, so the
        frozen-prior paths carry no extra state)."""
        if self.post is None:
            from repro.core.posterior import row_width
            U = self.n_units
            self.post = jnp.zeros((self.capacity, U, row_width(U)),
                                  jnp.float32)

    def update_posterior_rows(self, slots: np.ndarray,
                              vals: np.ndarray) -> None:
        """Scatter freshly folded posterior stats into the slots' device
        rows: ``vals`` is ``(len(slots), U, U+3)`` float32, computed on the
        host in a canonical fold order, so the stored rows are bit-identical
        at any shard count."""
        if len(slots) == 0:
            return
        self.ensure_posterior_rows()
        rows = jnp.asarray(self.device_rows(np.asarray(slots, np.int64)))
        self.post = self.post.at[rows].set(jnp.asarray(vals, jnp.float32))

    def posterior_rows(self, slots: np.ndarray) -> np.ndarray:
        """Read back the device posterior rows of a slot subset (tests,
        cross-engine/shard bit-identity checks)."""
        self.ensure_posterior_rows()
        rows = self.device_rows(np.asarray(slots, np.int64))
        return np.asarray(self.post[jnp.asarray(rows)])

    # ------------------------------------------------------------ lifecycle
    def admit(self, app_id: str, graph_idx: int, start: int, key_id: int,
              refresh_id: int = 0, deadline: Optional[float] = None,
              stretch: float = 1.0) -> int:
        """Take a free slot for a new application (grow by doubling when the
        arena is full).  The slot comes from the shard with the most free
        slots (lowest shard wins ties — the 1-shard path is unchanged) and
        is marked dirty — it must be walked before its first rank is
        consumed (its result rows are a previous tenant's or zeros)."""
        if not self._free_count():
            self._grow()
        shard = max(range(self.n_shards), key=lambda s: len(self._frees[s]))
        i = self._frees[shard].pop()
        self.ids[i] = app_id
        self.slot[app_id] = i
        self._occ[i] = True
        self.live += 1
        self.graph_idx[i] = graph_idx
        self.start[i] = start
        self.executed[i] = 0.0
        self.attained[i] = 0.0
        self.key_id[i] = key_id
        self.refresh_id[i] = refresh_id
        self.deadline[i] = np.inf if deadline is None else deadline
        self.stretch[i] = stretch
        self.ov_counts[i] = 0
        self._add_dirty(i)
        return i

    def admit_many(self, rows: Sequence[tuple]) -> np.ndarray:
        """Admit a batch in one call: ``rows`` is a sequence of
        ``(app_id, graph_idx, start, key_id, deadline)``.  Slot choice
        (shard balancing, grow timing) is IDENTICAL to calling
        :meth:`admit` per row in order, but the per-slot column writes land
        as one vectorized scatter per column — the array-native admission
        path for arrival bursts.  Returns the assigned slot ids."""
        n = len(rows)
        slots = np.empty(n, np.int64)
        for j, (app_id, *_rest) in enumerate(rows):
            if not self._free_count():
                self._grow()
            shard = max(range(self.n_shards),
                        key=lambda s: len(self._frees[s]))
            i = self._frees[shard].pop()
            slots[j] = i
            self.ids[i] = app_id
            self.slot[app_id] = i
            self._dirty[i % self.n_shards].add(i)
        self._occ[slots] = True
        self.live += n
        self.graph_idx[slots] = [r[1] for r in rows]
        self.start[slots] = [r[2] for r in rows]
        self.executed[slots] = 0.0
        self.attained[slots] = 0.0
        self.key_id[slots] = [r[3] for r in rows]
        self.refresh_id[slots] = 0
        self.deadline[slots] = [np.inf if r[4] is None else r[4]
                                for r in rows]
        self.stretch[slots] = 1.0
        self.ov_counts[slots] = 0
        return slots

    def retire(self, app_id: str) -> None:
        """Release an application's slot back to its shard's free-list.  The
        row's values stay in place (stale-but-in-bounds — dispatches mask
        holes), ready to be overwritten by the next admit."""
        i = self.slot.pop(app_id, None)
        if i is None:
            return
        if self.ov_counts[i].any():
            self.override_apps -= 1
        self.ids[i] = None
        self._occ[i] = False
        self.live -= 1
        self.ov_counts[i] = 0
        self._dirty[i % self.n_shards].discard(i)
        self.rank_dirty.discard(i)
        self._frees[i % self.n_shards].append(i)

    # --------------------------------------------------------------- events
    def set_unit(self, app_id: str, unit_idx: int) -> None:
        i = self.slot[app_id]
        self.start[i] = unit_idx
        self.executed[i] = 0.0
        self._add_dirty(i)

    def add_progress(self, app_id: str, delta: float) -> None:
        # progress does NOT dirty the slot: the TOTAL-demand histogram stays
        # valid and rank-in-place re-ranks at the new attained each tick;
        # only the rank mirror goes stale
        i = self.slot[app_id]
        self.executed[i] += delta
        self.attained[i] += delta
        self.rank_dirty.add(i)

    def set_override(self, app_id: str, unit_idx: int,
                     arr: np.ndarray) -> None:
        i = self.slot[app_id]
        arr = np.asarray(arr, np.float32)[:self.max_samples]
        if len(arr) == 0:
            return
        self._grow_override_width(len(arr))
        arr = arr[:self.ov_samples.shape[2]]
        if not self.ov_counts[i].any():
            self.override_apps += 1
        self.ov_samples[i, unit_idx, :len(arr)] = arr
        self.ov_counts[i, unit_idx] = len(arr)
        self._add_dirty(i)

    def set_stretch(self, app_id: str, stretch: float) -> None:
        self.stretch[self.slot[app_id]] = stretch

    def bump_refresh(self, slots: np.ndarray) -> None:
        self.refresh_id[slots] += 1

    # --------------------------------------------------------------- repack
    def maybe_repack(self, occupancy_threshold: float = 0.25,
                     min_capacity: int = 64) -> Optional[Dict[int, int]]:
        """Shrink the arena when occupancy fell below the threshold (and a
        smaller power of two actually fits).  Returns the old->new slot map
        when a repack happened, else None.  Call ONLY at a tick boundary —
        slot ids change across this epoch."""
        cap = self.capacity
        target = max(_pow2_ceil(max(self.live, 1)), min_capacity,
                     self.n_shards)
        if cap <= min_capacity or self.live > occupancy_threshold * cap \
                or target >= cap:
            return None
        return self.repack(target)

    def repack(self, new_capacity: Optional[int] = None) -> Dict[int, int]:
        """Rebuild the arena at ``new_capacity`` (default: smallest fitting
        power of two), renumbering live slots densely in ascending old-slot
        order.  Every host row, host mirror, and device-resident result row
        is remapped — persisted histograms survive, so a repack triggers no
        re-walk.  Bumps ``repack_epoch``; any slot id taken before this call
        is invalid after it."""
        old_cap, n = self.capacity, self.n_shards
        new_cap = max(_pow2_ceil(new_capacity or max(self.live, 1)), n, 1)
        old_slots = self.occupied()                       # ascending
        if len(old_slots) > new_cap:
            raise ValueError(f"repack to {new_cap} < live {len(old_slots)}")
        new_slots = np.arange(len(old_slots), dtype=np.int64)
        mapping = dict(zip(old_slots.tolist(), new_slots.tolist()))

        src = np.zeros(new_cap, np.int64)                 # old slot per new
        src[new_slots] = old_slots
        fill = np.zeros(new_cap, bool)
        fill[new_slots] = True
        for name in self._ROWS + (("trig", "reach")
                                  if self.trig is not None else ()) \
                + (("a_att",) if self.a_att is not None else ()):
            a = getattr(self, name)
            b = np.zeros((new_cap,) + a.shape[1:], a.dtype)
            b[fill] = a[src[fill]]
            setattr(self, name, b)
        self.deadline[~fill] = np.inf
        self.stretch[~fill] = 1.0
        if self.trig is not None:
            self.trig[~fill] = ARRIVAL_NEVER

        # device rows: one gather in the NEW shard-major row order (hole
        # rows read row 0 — garbage-in-bounds, masked like any other hole)
        if self.d_probs is not None or self.a_hist is not None \
                or self.post is not None:
            new_cs = new_cap // n
            rows = np.arange(new_cap, dtype=np.int64)
            nslot = (rows % new_cs) * n + rows // new_cs  # slot per new row
            old_row = np.where(fill[nslot],
                               (src[nslot] % n) * (old_cap // n)
                               + src[nslot] // n, 0)
            gidx = jnp.asarray(old_row)
            for name in ("d_probs", "d_edges", "a_hist", "a_lo", "a_span",
                         "a_reach", "post"):
                a = getattr(self, name)
                if a is not None:
                    setattr(self, name, a[gidx])

        old_ids = self.ids
        self.ids = [None] * new_cap
        for old, new in mapping.items():
            self.ids[new] = old_ids[old]
            self.slot[old_ids[old]] = new
        self._occ = fill
        self._frees = [[s for s in range(new_cap - n + sh, sh - 1, -n)
                        if not fill[s]] for sh in range(n)]
        remap = lambda ss: {mapping[s] for s in ss if s in mapping}  # noqa: E731
        old_dirty = self.dirty
        self._dirty = [set() for _ in range(n)]
        for s in remap(old_dirty):
            self._dirty[s % n].add(s)
        self.rank_dirty = remap(self.rank_dirty)
        self.repack_epoch += 1
        return mapping

    # ------------------------------------------------------------- dispatch
    def gather(self, slots: np.ndarray,
               pad_to: Optional[int] = None) -> Tuple[np.ndarray, ...]:
        """Padded dispatch view of a slot subset, padded (default: to a
        power of two) by repeating the first row (padding rows are
        valid-but-discarded)."""
        n = len(slots)
        ap = max(pad_to if pad_to is not None else _pow2_ceil(n), 1)
        pad_slot = int(slots[0]) if n else 0
        idx = np.concatenate([np.asarray(slots, np.int64),
                              np.full(ap - n, pad_slot, np.int64)])
        return (self.graph_idx[idx], self.start[idx], self.executed[idx],
                self.attained[idx], self.key_id[idx], self.refresh_id[idx],
                self.stretch[idx], self.ov_samples[idx], self.ov_counts[idx])


def build_queue_state(packed: PackedKB, apps: Sequence, kb_token=None,
                      n_shards: int = 1) -> QueueState:
    """Rebuild a QueueState from live AppRuntime records (used on first
    fused refresh and whenever the packed KB tables change shape/content).
    Every admitted slot starts dirty, so the first delta tick after a
    rebuild re-walks the whole queue."""
    qs = QueueState(packed, capacity=max(len(apps), 64), n_shards=n_shards)
    qs.kb_token = kb_token
    for a in apps:
        g = packed.graph_index[a.app_name]
        start = (packed.unit_index[g][a.current_unit] if a.current_unit
                 else int(packed.entry[g]))
        i = qs.admit(a.app_id, g, start, a.key_id, a.refreshes,
                     deadline=a.deadline,
                     stretch=getattr(a, "queue_stretch", 1.0))
        qs.executed[i] = a.attained_in_unit
        qs.attained[i] = a.attained
        for name, arr in (a.overrides or {}).items():
            uidx = packed.unit_index[g]
            if name in uidx:
                qs.set_override(a.app_id, uidx[name], arr)
    return qs
