"""HermesScheduler: the global queue manager (Fig. 4).

Holds the PDGraph knowledge base, tracks per-application runtime state,
refreshes scheduling priorities at bucket-period granularity, performs online
demand refinement on unit completion, and emits prewarm signals.

The scheduler is host-agnostic: both the discrete-event cluster simulator
(paper-scale experiments) and the real JAX serving engine drive it through the
same ``on_*`` callbacks; in a production deployment these arrive over RPC
(the paper uses ZeroMQ — see DESIGN.md §3 for the transport swap).
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax

from repro.core import correlation as C
from repro.core.pdgraph import (PDGraph, mc_service_samples_batch,
                                pack_graphs)
from repro.core.policies import (AppView, GittinsPolicy, Policy, VTCPolicy,
                                 make_policy)
from repro.core.arena import build_queue_state
from repro.core.posterior import (END, Observation, PosteriorConfig,
                                  PosteriorState, row_width)
from repro.core.prewarm import (PrewarmPlan, PrewarmSignal,
                                build_prewarm_table)
from repro.core.refresh_config import RefreshConfig
from repro.core.refresh_mesh import RefreshMesh, refresh_ranks_mesh
from repro.core.refresh_pipeline import refresh_ranks_delta
from repro.runtime.tracing import span


@dataclass
class AppRuntime:
    app_id: str
    app_name: str
    tenant: str
    arrival: float
    deadline: Optional[float] = None
    current_unit: Optional[str] = None
    unit_start: float = 0.0
    attained: float = 0.0                 # total service received (sec)
    attained_in_unit: float = 0.0
    done: bool = False
    overrides: Dict[str, np.ndarray] = field(default_factory=dict)
    view: Optional[AppView] = None
    oracle_remaining: Optional[float] = None
    key_id: int = 0                       # stable per-app RNG stream id
    refreshes: int = 0                    # per-app view-refresh counter
    queue_stretch: float = 1.0            # observed wall/service EWMA (§3.4)


class HermesScheduler:
    def __init__(self, knowledge_base: Dict[str, PDGraph],
                 policy: str = "gittins", *,
                 t_in: float = 1e-4, t_out: float = 2e-3,
                 K: float = 0.5, n_buckets: int = 10,
                 refine: bool = True, prewarm: bool = True,
                 mc_walkers: int = 512, seed: int = 0,
                 refresh: Optional[RefreshConfig] = None,
                 compact_after: int = 16, compact_shrink: int = 4,
                 warmup_table: Optional[Dict[str, float]] = None,
                 posterior: Optional[PosteriorConfig] = None):
        self.kb = knowledge_base
        self.policy: Policy = make_policy(policy) if policy != "gittins" \
            else make_policy(policy, n_buckets=n_buckets)
        self.t_in, self.t_out = t_in, t_out
        self.K = K
        self.n_buckets = n_buckets
        self.refine = refine
        self.prewarm_enabled = prewarm
        self.mc_walkers = mc_walkers
        self._mc_walkers_base = mc_walkers
        self._walker_cap: Optional[int] = None
        # The refresh backbone is configured by ONE validated RefreshConfig
        # (see repro.core.refresh_config).  A bare scheduler refreshes on
        # the composed host path; the simulator's SimConfig is where
        # fused_delta is the default.
        rc = refresh if refresh is not None else RefreshConfig(mode="composed")
        self.refresh_config = rc
        self.mode = rc.mode
        self.delta_full_threshold = rc.delta_full_threshold
        self.queue_delay_correction = rc.queue_delay_correction
        # Mesh sharding: partition the slot arena over mesh_shards devices
        # and run the whole delta pipeline per shard in one shard_map
        # dispatch (bit-identical to the 1-shard path for the same
        # placement).  mesh_shards=1 runs the sharded pipeline on a
        # degenerate one-device mesh (the scaling baseline); None keeps the
        # single-arena refresh_ranks_delta path.
        self.refresh_mesh: Optional[RefreshMesh] = None
        if rc.mesh_shards is not None:
            self.refresh_mesh = RefreshMesh(rc.mesh_shards)
        self._stretch_alpha = 0.3       # queue-wait EWMA smoothing
        self.walker = rc.walker
        self.rank_in_kernel = rc.rank_in_kernel
        self.lane_balance = rc.lane_balance
        self.compact_after = compact_after
        self.compact_shrink = compact_shrink
        self.apps: Dict[str, AppRuntime] = {}
        # live subset of `apps`: the refresh tick iterates only this, and
        # retired apps drop their sample arrays, so an unbounded open-arrival
        # stream costs O(live queue) per tick, not O(total arrivals)
        self._live: Dict[str, AppRuntime] = {}
        self._seed = seed
        self._base_key = jax.random.PRNGKey(seed)
        self._app_seq = itertools.count()
        self._packed = None               # (kb versions, PackedKB) cache
        self._qstate = None               # device-path slot store (lazy)
        self.fused_spill = 0              # walkers truncated by compaction
        # single-arena delta dispatches and their host<->device transfers
        self.refresh_stats = {"event_dispatches": 0, "tick_dispatches": 0,
                              "h2d": 0, "d2h": 0, "d2h_async": 0}
        self.warmup_table = warmup_table  # per-key warm-up cost overrides
        self._prewarm_tab = None          # (kb token, PrewarmTable) cache
        self.prewarm_plan: Optional[PrewarmPlan] = None   # last device plan
        # mesh fast path: app_id -> rank dict maintained incrementally (only
        # re-ranked slots are touched per tick); callers get a shallow copy
        self._mesh_ranks: Optional[Dict[str, float]] = None
        self._mesh_ranks_qs = None        # owning QueueState (invalidation)
        # per-backend service-stretch estimates (straggler watchdog feed):
        # the demand model's consumers scale wall estimates by these
        self.backend_slowdown: Dict[str, float] = {}
        # Online posterior learning (repro.core.posterior): observations
        # buffer host-side and fold into per-graph conjugate statistics at
        # the next delta tick, which scatters each about-to-walk slot's
        # device posterior row right before its walk.  None (the default)
        # allocates nothing and leaves every dispatch bit-identical.
        if posterior is not None and self.mode != "fused_delta":
            raise ValueError(
                "posterior learning rides the delta tick's walked-slot "
                f"scatter; it requires mode='fused_delta' (got {self.mode!r})")
        self.posterior = posterior
        self._post_state: Optional[PosteriorState] = \
            PosteriorState() if posterior is not None else None
        self._post_pending: List[Observation] = []
        self._post_cache: Dict[str, np.ndarray] = {}   # name -> (U, U+3) row
        self._post_cache_token = None
        for g in self.kb.values():
            C.apply_masks(g)

    # ------------------------------------------------------------ internals
    def _app_key(self, app: AppRuntime):
        """Deterministic per-(app, refresh) key — the same chain the batched
        walk derives, so a single-app refresh draws bit-identical MC
        samples."""
        k = jax.random.fold_in(self._base_key, app.key_id)
        return jax.random.fold_in(k, app.refreshes)

    def _packed_kb(self):
        versions = tuple(sorted((n, g.version) for n, g in self.kb.items()))
        if self._packed is None or self._packed[0] != versions:
            self._packed = (versions,
                            pack_graphs(self.kb, self.t_in, self.t_out))
        return self._packed[1]

    def _delta_active(self) -> bool:
        """The device pipeline computes Gittins ranks AND the composite
        policies' triage quantiles, so in ``fused_delta`` mode it engages
        for every fused-capable policy (gittins, hermes_ddl, lstf at the
        stock quantiles); anything else still needs raw host-side demand
        samples and falls back to the composed path."""
        return self.mode == "fused_delta" and \
            bool(getattr(self.policy, "fused_capable", False))

    @property
    def _with_triage(self) -> bool:
        """Composite policies need the device triage scalars; plain
        Gittins skips computing them (keeps its dispatch's cost and jit
        cache unchanged)."""
        return type(self.policy) is not GittinsPolicy

    @property
    def prewarm_batched(self) -> bool:
        """True when prewarm planning rides the device refresh dispatch
        (one batched PrewarmPlan per tick) instead of the per-app
        ``prewarm_signals`` calls."""
        return self.prewarm_enabled and self._delta_active()

    def _prewarm_table(self):
        """PrewarmTable aligned with the current packed KB (rebuilt whenever
        record_trial bumps a graph version and the KB is repacked)."""
        from repro.core.hermeslet import warmup_time_for
        packed = self._packed_kb()
        token = self._packed[0]
        if self._prewarm_tab is None or self._prewarm_tab[0] != token:
            tab = build_prewarm_table(
                self.kb, packed,
                lambda k: warmup_time_for(k, self.warmup_table))
            self._prewarm_tab = (token, tab)
        return self._prewarm_tab[1]

    def take_prewarm_plan(self) -> Optional[PrewarmPlan]:
        """Hand the last device-dispatch PrewarmPlan to the host (simulator /
        engine) exactly once; None when nothing was planned since the last
        take."""
        plan, self.prewarm_plan = self.prewarm_plan, None
        return plan

    def _ensure_qstate(self):
        """Queue buffers are maintained incrementally by the on_* events;
        (re)built from scratch only on first use and when the packed KB
        tables change shape/content (record_trial bumps graph versions)."""
        packed = self._packed_kb()
        token = self._packed[0]
        if self._qstate is None or self._qstate.kb_token != token:
            self._qstate = build_queue_state(
                packed, list(self._live.values()), kb_token=token,
                n_shards=(self.refresh_mesh.n_shards if self.refresh_mesh
                          else 1))
        return self._qstate

    def _qstate_if_current(self):
        """PackedKB when the incremental QueueState may be mutated in place;
        None when there is none or the KB was repacked since it was built
        (then the stale buffers are dropped — unit indices/table shapes may
        have changed — and rebuilt wholesale on the next device refresh)."""
        if self._qstate is None:
            return None
        packed = self._packed_kb()
        if self._qstate.kb_token != self._packed[0]:
            self._qstate = None
            return None
        return packed

    def _total_samples(self, app: AppRuntime) -> np.ndarray:
        """TOTAL demand distribution = attained + MC(remaining)."""
        g = self.kb[app.app_name]
        rem = g.mc_service_samples(
            self._app_key(app), self.t_in, self.t_out,
            start_unit=app.current_unit,
            executed_in_unit=app.attained_in_unit,
            unit_sample_override=app.overrides or None,
            n_walkers=self.mc_walkers)
        app.refreshes += 1
        return app.attained + np.maximum(rem, 0.0)

    def _make_view(self, app: AppRuntime, samples: np.ndarray) -> None:
        app.view = AppView(app_id=app.app_id, tenant=app.tenant,
                           arrival=app.arrival, attained=app.attained,
                           total_samples=samples, deadline=app.deadline,
                           oracle_remaining=app.oracle_remaining)

    def _refresh_view(self, app: AppRuntime) -> None:
        self._make_view(app, self._total_samples(app))

    def _refresh_views(self, apps: List[AppRuntime]) -> None:
        """Refresh many views at once: one padded batched MC dispatch for
        the whole set instead of one walk per application."""
        if not apps:
            return
        if len(apps) == 1:
            self._refresh_view(apps[0])
            return
        packed = self._packed_kb()
        gi = np.asarray([packed.graph_index[a.app_name] for a in apps],
                        np.int32)
        start = np.asarray(
            [packed.unit_index[g][a.current_unit] if a.current_unit
             else packed.entry[g] for g, a in zip(gi, apps)], np.int32)
        rem = mc_service_samples_batch(
            packed, self._base_key,
            graph_idx=gi, start=start,
            executed=np.asarray([a.attained_in_unit for a in apps]),
            key_ids=np.asarray([a.key_id for a in apps], np.int32),
            refresh_ids=np.asarray([a.refreshes for a in apps], np.int32),
            overrides=[a.overrides or None for a in apps],
            n_walkers=self.mc_walkers)
        total = np.maximum(rem, 0.0)
        # float32 addend: bit-identical to the single-app path's
        # `attained + np.maximum(rem, 0.0)` float32 scalar promotion
        total += np.asarray([a.attained for a in apps],
                            np.float32)[:, None]
        for a, row in zip(apps, total):
            a.refreshes += 1
            self._make_view(a, row)

    def _priorities_delta(self, now: float,
                          app_ids: Optional[List[str]] = None, *,
                          caller: Optional[str] = None
                          ) -> Dict[str, float]:
        """The delta tick: drain the dirty set, walk ONLY those slots (full
        re-walk past the dirty-fraction threshold), re-rank from the
        persisted device histograms, and serve every live rank from the
        store — rank, triage scalars, prewarm rows.  Full ticks are the
        repack boundary (no slot id is held outside the store here) and,
        with prewarming, re-condition every trigger row on elapsed service.

        Event-path subset calls (``app_ids`` given) walk only the dirty
        slots the event actually touched; other dirty slots keep their mark
        and walk on the next full tick, so per-event cost stays sized by
        the event, not by unrelated queue churn.

        On the single arena the host work before and after
        :func:`refresh_ranks_delta` runs in its ``hermes.<path>.prepare``
        and ``.consume`` spans, and the dispatch is counted in
        ``refresh_stats`` under ``<path>_dispatches``; the policy's use of
        the store's columns runs in ``hermes.key``, inside ``.consume``.
        ``<path>`` is ``caller`` (``"tick"`` for a bucket tick, ``"event"``
        for an event batch) where the host names it, else ``tick`` for a
        full call and ``event`` for a subset one; it names the dispatch
        and changes nothing of what it does."""
        qs = self._ensure_qstate()
        if len(qs) == 0:
            return {}
        full = app_ids is None
        if self.refresh_mesh is not None:
            live, walked, tab = self._take_walk(qs, app_ids)
            return self._priorities_mesh(qs, live, walked, now, tab, full)
        path = caller or ("tick" if full else "event")
        with span(path + ".prepare"):
            live, walked, tab = self._take_walk(qs, app_ids)
        tick = refresh_ranks_delta(
            self._packed[1], qs, self._base_key, self._seed,
            walked=walked, n_walkers=self.mc_walkers,
            n_buckets=self.n_buckets, walker=self.walker,
            compact_after=self.compact_after,
            compact_shrink=self.compact_shrink,
            prewarm_table=tab, prewarm_k=self.K, retrigger=full,
            with_triage=self._with_triage, posterior=self.posterior,
            rank_in_kernel=self.rank_in_kernel, path=path)
        with span(path + ".consume"):
            self.fused_spill += tick.spill
            stats = self.refresh_stats
            stats[path + "_dispatches"] += 1
            stats["h2d"] += tick.h2d
            stats["d2h"] += tick.d2h
            stats["d2h_async"] += tick.d2h_async
            if full:
                qs.take_rank_dirty()     # arena-wide re-rank covered everyone
            if tab is not None:
                # full ticks re-conditioned EVERY slot's trigger rows on the
                # service attained since its walk, so the plan covers the
                # whole queue; event-path refreshes only re-planned the
                # walked rows
                plan_slots = qs.occupied() if full else walked
                if len(plan_slots):
                    self._stash_plan(PrewarmPlan.from_store(qs, plan_slots,
                                                            now, tab))
            if len(walked):
                qs.bump_refresh(walked)
                for s in walked:
                    self.apps[qs.ids[int(s)]].refreshes += 1
            with span("key"):
                return self._ranks_from_store(qs, live, tick.ranks, now)

    def _take_walk(self, qs, app_ids: Optional[List[str]]):
        """What a delta tick walks: ``(live apps it ranks, slots to walk,
        prewarm table | None)``.  A full tick (``app_ids`` None) repacks
        and drains the whole dirty set; an event drains only the touched
        slots' marks.  Pending posterior observations are flushed into the
        slots about to walk."""
        if app_ids is None:
            # repack epoch boundary: no slot id is held outside the store
            # between full ticks, so a shrink (mirrors remapped in place,
            # dispatch shapes retrace at the new capacity) is safe here
            qs.maybe_repack()
            live = list(self._live.values())
            walked = qs.take_dirty()
            if len(walked) >= self.delta_full_threshold * len(qs):
                # past the threshold the subset gather/scatter saves
                # nothing: fall back to re-walking the whole occupied set
                walked = qs.occupied()
        else:
            live = [self.apps[i] for i in app_ids
                    if i in self.apps and not self.apps[i].done]
            req = {qs.slot[a.app_id] for a in live}
            walked = np.asarray(sorted(qs.dirty_in(req)), np.int64)
            qs.clear_dirty(req)
        if self.posterior is not None:
            self._posterior_flush(qs, walked)
        tab = self._prewarm_table() if self.prewarm_batched else None
        return live, walked, tab

    def _priorities_mesh(self, qs, live: List[AppRuntime],
                         walked: np.ndarray, now: float, tab,
                         full: bool) -> Dict[str, float]:
        """The mesh-sharded delta tick: one shard_map dispatch walks each
        shard's dirty rows and re-ranks each shard's *stale* rows (walked ∪
        progressed); every other live rank is served from the store's host
        rank mirror without touching a device.  For the plain Gittins
        policy the whole consumption side is vectorized — no per-app view
        objects on the tick path at all."""
        within = None if full else {qs.slot[a.app_id] for a in live}
        stale = qs.take_rank_dirty(within)
        stale.update(int(s) for s in walked)
        ranked = np.asarray(sorted(stale), np.int64)

        def bookkeeping():
            # overlapped with the device walk (refresh id rows were already
            # snapshotted into the dispatch's carrier)
            if len(walked):
                qs.bump_refresh(walked)
                for s in walked:
                    self.apps[qs.ids[int(s)]].refreshes += 1

        tick = refresh_ranks_mesh(
            self._packed[1], qs, self._base_key, self._seed,
            mesh=self.refresh_mesh, walked=walked, ranked=ranked,
            n_walkers=self.mc_walkers, n_buckets=self.n_buckets,
            walker=self.walker, compact_after=self.compact_after,
            compact_shrink=self.compact_shrink,
            prewarm_table=tab, prewarm_k=self.K, retrigger=full,
            host_work=bookkeeping, with_triage=self._with_triage,
            posterior=self.posterior,
            rank_in_kernel=self.rank_in_kernel,
            lane_balance=self.lane_balance)
        self.fused_spill += tick.spill
        if tab is not None:
            plan_slots = qs.occupied() if full else walked
            if len(plan_slots):
                self._stash_plan(PrewarmPlan.from_store(qs, plan_slots,
                                                        now, tab))
        if type(self.policy) is GittinsPolicy:
            # incremental consumption: only the re-ranked slots touch the
            # cached dict (retires prune it in _retire; a store rebuild
            # resets it), so per-tick host cost is O(churn), not O(live).
            # Event-path subset refreshes MUST update it too — they re-walk
            # slots and drain their marks, so the next full tick would
            # otherwise serve the pre-event rank forever
            cache = self._mesh_ranks
            if cache is not None and self._mesh_ranks_qs is qs:
                for s, r in zip(ranked.tolist(), tick.ranks.tolist()):
                    cache[qs.ids[s]] = r
            if not full:
                slots = np.asarray([qs.slot[a.app_id] for a in live],
                                   np.int64)
                ids = [qs.ids[s] for s in slots.tolist()]
                return dict(zip(ids, qs.rank[slots].tolist()))
            if cache is None or self._mesh_ranks_qs is not qs:
                occ = qs.occupied()
                cache = dict(zip([qs.ids[s] for s in occ.tolist()],
                                 qs.rank[occ].tolist()))
                self._mesh_ranks, self._mesh_ranks_qs = cache, qs
            return dict(cache)
        return self._ranks_from_store(qs, live, qs.rank, now)

    def _ranks_from_store(self, qs, live: List[AppRuntime],
                          ranks_row: np.ndarray, now: float
                          ) -> Dict[str, float]:
        """Policy consumption straight off store columns: the device ranks
        (``ranks_row`` — the delta tick's full-arena rank vector, or the
        mesh's host rank mirror) and the triage scalar mirrors are gathered
        per-slot in vectorized reads and handed to the policy's
        ``ranks_columns`` twin; a policy without one (plain Gittins) takes
        the device ranks as they are.  No AppView objects are minted on
        this path.  ``attained``/``deadline`` come from the float64 host
        records (the float32 store mirrors round)."""
        if not live:
            return {}
        n = len(live)
        slots = np.asarray([qs.slot[a.app_id] for a in live], np.int64)
        ids = [a.app_id for a in live]
        g = np.asarray(ranks_row[slots], np.float32)
        if not getattr(self.policy, "columns_capable", False):
            return dict(zip(ids, g.tolist()))
        attained = np.fromiter((a.attained for a in live), np.float64,
                               count=n)
        deadline = np.fromiter(
            (np.inf if a.deadline is None else a.deadline for a in live),
            np.float64, count=n)
        ranks = self.policy.ranks_columns(
            now, g=g,
            sup=qs.sup[slots].astype(np.float64),
            opt=qs.opt[slots].astype(np.float64),
            mean=qs.mean[slots].astype(np.float64),
            attained=attained, deadline=deadline)
        return dict(zip(ids, (float(r) for r in ranks)))

    def _posterior_flush(self, qs, walked: np.ndarray) -> None:
        """Fold the pending observation buffer into the per-graph conjugate
        statistics and scatter ``row := graph stats`` for every about-to-walk
        slot.  Walked slots are exactly the slots whose estimates re-walk
        this tick — admitted slots are dirty, hence walked, hence flushed —
        so a slot's device posterior row always equals its graph's
        accumulated posterior as of its last walk, and freshly admitted
        instances inherit everything earlier instances learned (stale
        garbage from a slot's previous occupant is overwritten before it is
        ever sampled)."""
        if self._post_pending:
            for name in self._post_state.fold(self._post_pending):
                self._post_cache.pop(name, None)
            self._post_pending = []
        if len(walked) == 0:
            return
        packed = self._packed_kb()
        if self._post_cache_token != self._packed[0]:
            # KB repack: packed unit order may have moved — rematerialize
            self._post_cache = {}
            self._post_cache_token = self._packed[0]
        U = qs.n_units
        vals = np.empty((len(walked), U, row_width(U)), np.float32)
        for i, s in enumerate(np.asarray(walked).tolist()):
            name = self.apps[qs.ids[int(s)]].app_name
            row = self._post_cache.get(name)
            if row is None:
                uidx = packed.unit_index[packed.graph_index[name]]
                order = sorted(uidx, key=uidx.get)
                row = self._post_state.graph_row(name, order, U)
                self._post_cache[name] = row
            vals[i] = row
        qs.update_posterior_rows(np.asarray(walked, np.int64), vals)

    def _stash_plan(self, plan: PrewarmPlan) -> None:
        """Accumulate plans until the host takes them (several subset
        refreshes — or several shards' rows — may land between two
        take_prewarm_plan calls).  ``PrewarmPlan.merge`` dedups on (app,
        class) with the NEWEST trigger winning — later refreshes have
        fresher arrival estimates — so the stash is bounded by live-apps x
        classes even if no host ever takes it."""
        if len(plan) == 0:
            return
        prev = self.prewarm_plan
        if prev is None or len(prev) == 0:
            self.prewarm_plan = plan
            return
        self.prewarm_plan = prev.merge(plan, self._live.__contains__)

    # -------------------------------------------------------------- events
    def on_arrival(self, app_id: str, app_name: str, now: float, *,
                   tenant: str = "default",
                   deadline: Optional[float] = None) -> None:
        g = self.kb[app_name]
        app = AppRuntime(app_id=app_id, app_name=app_name, tenant=tenant,
                         arrival=now, deadline=deadline,
                         current_unit=g.entry, unit_start=now,
                         key_id=next(self._app_seq))
        self.apps[app_id] = app
        self._live[app_id] = app
        packed = self._qstate_if_current()
        if packed is not None:
            gi = packed.graph_index[app_name]
            self._qstate.admit(app_id, gi, int(packed.entry[gi]), app.key_id,
                               deadline=deadline)
        # view stays stale until the next priorities() call, which refreshes
        # every stale view in one batched dispatch (in delta mode the admit
        # marked the slot dirty, so the next tick walks it)

    def _qstate_set_unit(self, app: AppRuntime, unit: Optional[str]) -> None:
        packed = self._qstate_if_current()
        if packed is None or app.app_id not in self._qstate.slot:
            return
        g = packed.graph_index[app.app_name]
        idx = packed.unit_index[g][unit] if unit else int(packed.entry[g])
        self._qstate.set_unit(app.app_id, idx)

    def on_unit_start(self, app_id: str, unit: str, now: float) -> None:
        app = self.apps[app_id]
        app.current_unit = unit
        app.unit_start = now
        app.attained_in_unit = 0.0
        self._qstate_set_unit(app, unit)

    def on_progress(self, app_id: str, service_delta: float) -> None:
        app = self.apps[app_id]
        app.attained += service_delta
        app.attained_in_unit += service_delta
        if app.view is not None:
            # the cached histogram of TOTAL demand stays valid: the next
            # priorities() re-ranks from it at the new attained
            app.view.attained = app.attained
        if self._qstate is not None and app_id in self._qstate.slot:
            self._qstate.add_progress(app_id, service_delta)
        if isinstance(self.policy, VTCPolicy):
            self.policy.account(app.tenant, service_delta)

    def on_unit_finish(self, app_id: str, unit: str,
                       observed: Dict[str, float], now: float,
                       next_unit: Optional[str]) -> None:
        """Online refinement: condition every downstream unit's demand on the
        just-observed execution (bucket-join + filter, §3.2).  With posterior
        learning enabled the completion also self-observes: the unit's
        model-space service (the ``trajectory_service`` formula over the
        observed token counts) and the taken branch feed the conjugate
        statistics, so hosts that already drive ``on_unit_finish`` need no
        extra observation calls."""
        app = self.apps[app_id]
        g = self.kb[app.app_name]
        if self.posterior is not None:
            svc = C.observed_service(observed, self.t_in, self.t_out)
            self._post_pending.append(
                (app.app_name, unit, "demand", svc))
            self._post_pending.append(
                (app.app_name, unit, "branch",
                 next_unit if next_unit is not None else END))
        if self.refine:
            # one KB-version check for the whole refinement loop
            qs_packed = self._qstate_if_current()
            # refine every unit whose demand is correlation-masked on the
            # just-finished one (direct successors and 2-hop pairs alike)
            prefix = unit + "|"
            for name, node in g.units.items():
                if name == unit:
                    continue
                if not any(k.startswith(prefix) and v
                           for k, v in node.corr_mask.items()):
                    continue
                cond = C.conditional_samples(g, unit, name, observed,
                                             self.t_in, self.t_out)
                if cond is not None:
                    app.overrides[name] = cond
                    if qs_packed is not None and \
                            app_id in self._qstate.slot:
                        uidx = qs_packed.unit_index[
                            qs_packed.graph_index[app.app_name]]
                        if name in uidx:
                            self._qstate.set_override(app_id, uidx[name],
                                                      cond)
        if next_unit is None:
            self._retire(app)
        else:
            app.current_unit = next_unit
            app.unit_start = now
            app.attained_in_unit = 0.0
            self._qstate_set_unit(app, next_unit)
        if not app.done:
            app.view = None          # stale: re-estimated on next priorities()

    def on_app_complete(self, app_id: str) -> None:
        self._retire(self.apps[app_id])

    def _retire(self, app: AppRuntime) -> None:
        """Mark done and release the per-app demand state (sample arrays,
        refinement overrides); the AppRuntime shell stays in `apps` for
        host-side bookkeeping."""
        app.done = True
        app.current_unit = None
        app.view = None
        app.overrides.clear()
        self._live.pop(app.app_id, None)
        if self._mesh_ranks is not None:
            self._mesh_ranks.pop(app.app_id, None)
        if self._qstate is not None:
            self._qstate.retire(app.app_id)

    def on_app_shed(self, app_id: str) -> None:
        """Admission control dropped this application (terminal shed or
        deferral): retire its arena slot and demand state exactly once — a
        second shed / a completion racing a shed is a no-op."""
        app = self.apps.get(app_id)
        if app is None or app.done:
            return
        self._retire(app)

    def on_requeue(self, app_id: str, now: float) -> None:
        """A re-queued orphan unit re-entered the waiting queue: nothing
        about the app's PDGraph position changed (uncredited progress was
        lost with the backend), but its estimate should re-walk on the next
        delta tick so the rank reflects the re-submission."""
        app = self.apps.get(app_id)
        if app is None or app.done:
            return
        app.view = None
        if self._qstate is not None:
            self._qstate.mark_dirty(app_id)

    def set_walker_cap(self, cap: Optional[int]) -> None:
        """Load-adaptive degradation: cap the MC-refinement walker depth
        (``None`` restores the configured depth).  Cheaper refresh ticks
        exactly when the queue is largest; capped estimates are noisier, so
        hosts only engage this past the degradation watermark.  The cap is
        clamped to a power of two so the device dispatch adds at most one
        extra jit trace per distinct cap."""
        if cap is None:
            self._walker_cap = None
            self.mc_walkers = self._mc_walkers_base
            return
        cap = max(int(cap), 1)
        cap = 1 << (cap.bit_length() - 1)            # floor to power of two
        self._walker_cap = cap
        self.mc_walkers = min(self._mc_walkers_base, cap)

    def observe_unit_completion(self, app_id: str, unit: str,
                                service_s: float, *,
                                wall_s: Optional[float] = None,
                                backend: Optional[str] = None,
                                slowdown: Optional[float] = None) -> None:
        """ONE coherent observation feed for hosts that execute units outside
        ``on_unit_finish`` (the serving engine, external RPC drivers): the
        observed model-space service seconds feed the posterior demand
        statistics; ``wall_s`` (observed wall clock, when it differs from
        service) feeds the §3.4 queueing-delay stretch; ``backend`` +
        ``slowdown`` forward the straggler watchdog's estimate.  Each leg is
        a no-op when its feature is off, so calling this unconditionally is
        always safe."""
        if backend is not None and slowdown is not None:
            self.observe_backend_slowdown(backend, slowdown)
        if wall_s is not None:
            self.observe_queue_wait(app_id, max(wall_s - service_s, 0.0),
                                    service_s)
        if self.posterior is None:
            return
        app = self.apps.get(app_id)
        if app is None:
            return
        self._post_pending.append(
            (app.app_name, unit, "demand", float(service_s)))

    def observe_branch_taken(self, app_id: str, unit: str,
                             next_unit: Optional[str]) -> None:
        """Posterior branch feed: the application finished ``unit`` and
        moved to ``next_unit`` (None = terminal).  No-op without posterior
        learning."""
        if self.posterior is None:
            return
        app = self.apps.get(app_id)
        if app is None:
            return
        self._post_pending.append(
            (app.app_name, unit, "branch",
             next_unit if next_unit is not None else END))

    def observe_backend_slowdown(self, backend_id: str,
                                 slowdown: float) -> None:
        """Straggler-watchdog feed: record a backend's estimated service
        stretch (1.0 = full speed).  ``service_slowdown`` aggregates these
        for the demand model's wall-time consumers (admission estimates,
        prewarm stretch)."""
        if slowdown <= 1.0:
            self.backend_slowdown.pop(backend_id, None)
        else:
            self.backend_slowdown[backend_id] = float(slowdown)

    def service_slowdown(self, kind: Optional[str] = None) -> float:
        """Max live stretch estimate across flagged backends (of one kind
        when given — backend ids are ``{kind}{index}``); 1.0 when clean."""
        vals = [v for k, v in self.backend_slowdown.items()
                if kind is None or k.startswith(kind)]
        return max(vals) if vals else 1.0

    def demand_triage(self, app_id: str) -> Optional[Tuple[float, float]]:
        """(attained service, optimistic TOTAL demand) of one application —
        the same instance-level estimate the composite policies' hopeless
        gate reads on the host path: the HOPELESS_Q sample quantile of its
        view.  ``None`` without a view (before the app's first composed
        refresh, and always on the device path, which mints none);
        admission then falls back to its name-level prior."""
        from repro.core.policies import HOPELESS_Q
        app = self.apps.get(app_id)
        if app is None or app.done or app.view is None:
            return None
        return app.attained, float(np.quantile(app.view.total_samples,
                                               HOPELESS_Q))

    def set_oracle(self, app_id: str, remaining: float) -> None:
        app = self.apps[app_id]
        app.oracle_remaining = remaining
        if app.view is not None:
            app.view.oracle_remaining = remaining

    # ------------------------------------------------------------ decisions
    def priorities(self, now: float,
                   app_ids: Optional[List[str]] = None, *,
                   caller: Optional[str] = None) -> Dict[str, float]:
        """Rank live applications (lower = run first).  Called once per
        bucket period — the Fig. 15 hot path.  ``app_ids`` restricts the
        ranking to a subset (ranks are per-app independent, so hosts can
        re-rank just the applications an event touched between full ticks).
        ``caller`` (``"tick"`` or ``"event"``) names the single-arena
        delta dispatch's spans and counter by the host's call that made
        it; it changes no rank.
        """
        if self._delta_active():
            return self._priorities_delta(now, app_ids, caller=caller)
        if app_ids is None:
            live = list(self._live.values())
        else:
            live = [self.apps[i] for i in app_ids
                    if i in self.apps and not self.apps[i].done]
        if getattr(self.policy, "view_free", False):
            # rank reads only per-app scheduler state (arrival / tenant /
            # deadline — AppRuntime carries the same fields AppView does),
            # never the demand estimate: skip the MC view refresh entirely.
            # Rank values are identical to the refreshed-view path.
            if not live:
                return {}
            ranks = self.policy.ranks(live, now)
            return {a.app_id: float(r) for a, r in zip(live, ranks)}
        self._refresh_views([a for a in live if a.view is None])
        views = [a.view for a in live]
        if not views:
            return {}
        ranks = self.policy.ranks(views, now)
        return {a.app_id: float(r) for a, r in zip(live, ranks)}

    def priorities_arrays(self, now: float,
                          app_ids: Optional[List[str]] = None, *,
                          caller: Optional[str] = None
                          ) -> Tuple[List[str], np.ndarray]:
        """Array-facing twin of :meth:`priorities`: ``(app_ids, ranks)``
        with the ranks as one float64 vector instead of a dict of boxed
        floats.  Array-native hosts (the simulator's calendar engine)
        scatter the vector straight into their rank columns — at 100k live
        applications the per-app dict build is itself a per-tick O(Q) host
        cost worth deleting.  Fast paths:

        * view-free policies rank straight off the AppRuntime records (no
          view refresh, no dict);
        * Gittins over the mesh/delta store serves slot-aligned rank
          mirrors gathered in one vectorized read;
        * everything else falls back through :meth:`priorities`.

        Rank values are bit-identical to :meth:`priorities` for the same
        state."""
        if getattr(self.policy, "view_free", False):
            if app_ids is None:
                live = list(self._live.values())
            else:
                live = [self.apps[i] for i in app_ids
                        if i in self.apps and not self.apps[i].done]
            if not live:
                return [], np.zeros(0)
            return ([a.app_id for a in live],
                    np.asarray(self.policy.ranks(live, now), np.float64))
        d = self.priorities(now, app_ids, caller=caller)
        return list(d), np.fromiter(d.values(), np.float64, count=len(d))

    def on_arrivals(self, items: List[tuple], now: float) -> None:
        """Batch admission: ``items`` is a list of ``(app_id, app_name,
        tenant, deadline)``.  Equivalent to calling :meth:`on_arrival` per
        item in order (same slot assignment, same dirty marks), but the
        slot-store writes land through one ``admit_many`` call — the
        array-native host path for arrival bursts."""
        packed = self._qstate_if_current()
        rows = []
        for app_id, app_name, tenant, deadline in items:
            g = self.kb[app_name]
            app = AppRuntime(app_id=app_id, app_name=app_name, tenant=tenant,
                             arrival=now, deadline=deadline,
                             current_unit=g.entry, unit_start=now,
                             key_id=next(self._app_seq))
            self.apps[app_id] = app
            self._live[app_id] = app
            if packed is not None:
                gi = packed.graph_index[app_name]
                rows.append((app_id, gi, int(packed.entry[gi]),
                             app.key_id, deadline))
        if rows:
            self._qstate.admit_many(rows)

    def refresh_tick(self, now: float, *,
                     resample: bool = False) -> Dict[str, float]:
        """The bucket-tick refresh: re-rank the whole queue.  With
        ``resample=True`` every live demand estimate is first re-drawn from
        the PDGraphs (one batched MC dispatch) — the full Fig. 15 refresh
        cost.  In
        ``fused_delta`` mode resampling is demand-driven instead: only the
        slots whose PDGraph position changed since the last tick (the dirty
        set) are re-walked, everyone else re-ranks in place from persisted
        device histograms — the §3.3 observation that estimates only move
        when the graph position does."""
        if resample and not self._delta_active():
            for a in self._live.values():
                a.view = None
        return self.priorities(now)

    def observe_queue_wait(self, app_id: str, wait_s: float,
                           service_s: float) -> None:
        """Queueing-delay correction feed (§3.4 refinement): hosts report
        each task's observed queue wait at start; the scheduler keeps a
        per-app EWMA of the wall/service *stretch* factor, which the device
        prewarm reduction uses to convert arrival quantiles (cumulative
        service seconds) into wall-clock trigger times.  No-op unless
        ``queue_delay_correction`` is enabled (default off — the §3.4 paper
        model assumes continuous execution)."""
        if not self.queue_delay_correction:
            return
        app = self.apps.get(app_id)
        if app is None or app.done:
            return
        if service_s <= 1e-3:
            return      # degenerate task: wait/service ratio is meaningless
        # clamp: one pathological observation must not blow the EWMA up and
        # push every trigger past the horizon (recovery takes ~1/alpha obs)
        obs = min((max(wait_s, 0.0) + service_s) / service_s, 100.0)
        app.queue_stretch += self._stretch_alpha * (obs - app.queue_stretch)
        if self._qstate is not None and app_id in self._qstate.slot:
            self._qstate.set_stretch(app_id, app.queue_stretch)

    def prewarm_signals(self, app_id: str, now: float,
                        warmup_time_of, is_warm) -> List[PrewarmSignal]:
        if not self.prewarm_enabled:
            return []
        app = self.apps[app_id]
        if app.done or app.current_unit is None:
            return []
        g = self.kb[app.app_name]
        return list(PrewarmPlan.one_hop(
            g, app_id, app.current_unit, app.unit_start, now, self.K,
            warmup_time_of, is_warm, self.t_in, self.t_out).signals())
