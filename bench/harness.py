"""Benchmark harness of the Hermes control plane: one run of one cell.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); each metric is read by its own reader
(``bench/metrics/<name>.py``, a ``read(record)`` function).  Nothing here
names a cell, a configuration, a mix or a metric.

A run drives the program's normal path, ``ClusterSim.run`` with the
configuration's ``SimConfig``, from the trace that ``--seed`` generates.
Set-up is everything before the window: imports, device, knowledge base,
trace, the burst's admission (where the mix has one), and the run's own
ticks, simulated until the mix's ``warmup`` says: at least ``min_sim_s``
simulated seconds, the last ``quiet_ticks`` full ticks without a new
program.  The window then lasts ``--seconds`` of
wall time, timed at the simulator's ``progress`` callback: each interval
between two calls is one micro-batch.  The layers are timed from outside,
by wrapping, here, the calls into them.  After the window the sampled
dispatches are compared with the plain reference (``bench/check.py``).
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time
import warnings
from pathlib import Path
from typing import Callable, Dict, List, Optional

from bench import check

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
FALLBACK_WARNING = "pdgraph_walk: requested impl='pallas' fell back"
SPAN = "bench."                     # prefix of the harness's host spans
SETUP_LIMIT_S = 1000.0              # a set-up past this fails the run
TICK_CAPTURES = 5                   # full ticks kept for the check (+ last)
EVENT_CAPTURES = 24                 # event-path dispatches kept


class RunError(RuntimeError):
    """A run that cannot give a result."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ files
def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> Dict:
    return load_json(root / "BENCHMARK.json")


def cell_parts(bm: Dict, workload: str, bench_dir: Path = BENCH):
    """(cell, configuration file, traffic file, end-to-end metric names,
    per-layer metric names) of one workload, all found by name."""
    cells = {c["name"]: c for c in bm["workloads"]}
    if workload not in cells:
        raise RunError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = load_json(bench_dir / "configs" / f"{cell['config']}.json")
    traffic = load_json(bench_dir / "traffic" / f"{cell['traffic']}.json")

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = [m["name"] for m in bm["end_to_end"] if applies(m)]
    layer = [m["name"] for m in bm["per_layer"] if applies(m)]
    units = {m["name"]: m["unit"] for m in bm["end_to_end"] + bm["per_layer"]}
    return cell, config, traffic, e2e, layer, units


def metric_reader(name: str, bench_dir: Path = BENCH) -> Callable:
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_limits(bench_dir: Path = BENCH) -> Dict[str, float]:
    return {k: float(v["limit"])
            for k, v in load_json(bench_dir / "limits.json").items()}


def seeds_from(seed: int) -> Dict[str, int]:
    """Every seed of a run, derived from ``--seed`` (any whole number).
    The first word is left unused so that each seed keeps the inputs its
    readings in ``PERF.md`` were taken with."""
    w = np.random.SeedSequence(abs(int(seed))).generate_state(4)
    return {"sim": int(w[1] & 0x7FFFFFFF), "traffic": int(w[2]),
            "sample": int(w[3])}


# ----------------------------------------------------------------- device
def device_info(chips: int, require_tpu: bool = True) -> Dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise RunError(f"JAX finds no TPU (platform {info['platform']!r}); "
                       "the benchmark does not run on the CPU")
    if info["count"] < chips:
        raise RunError(f"the cell needs {chips} chips, JAX sees "
                       f"{info['count']}")
    return info


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache at a fixed directory of the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(root / ".jax_cache")
    Path(path).mkdir(parents=True, exist_ok=True)   # JAX writes, never makes
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Programs lowered, persistent-cache hits and misses since start."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.lowered = self.hits = self.misses = 0
        self.seconds = 0.0
        lower_ev = dispatch.JAXPR_TO_MLIR_MODULE_EVENT

        def on_duration(event, secs, **_):
            if event == lower_ev:
                self.lowered += 1
            if event.startswith("/jax/core/compile/"):
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return (self.lowered, self.hits, self.misses)


# ---------------------------------------------------------------- layers
class Layers:
    """Wrappers, installed from here, around the calls into each layer.

    * ``ClusterSim._refresh_ranks`` (instance): bucket ticks pass
      ``touched=``, event batches an id list; marks the micro-batch, and
      classes every dispatch made inside the call by that caller, whatever
      the call re-ranks (an event batch of a policy whose ranks hang on
      time re-ranks everything);
    * ``repro.core.scheduler.refresh_ranks_delta``: one refresh dispatch
      (its ``walked`` rows, spill, result), counted and sampled for the
      check; one with ``retrigger`` is the scheduler's call without ids,
      which drains the whole dirty set;
    * the scheduler's application events (``on_arrivals``,
      ``on_unit_start``, ``on_unit_finish``, ``on_requeue``,
      ``on_progress``, ``on_app_complete``, ``on_app_shed``): which
      applications are due a walk, and the service credited to each,
      summed here in float32 as the scheduler's own accumulators sum it;
    * with ``timed``: wall time of each refresh call, and host spans
      (``jax.profiler.TraceAnnotation``) around the drain, both refresh
      paths, the prewarm-plan apply and the reschedule.
    """

    def __init__(self, sim, *, timed: bool, sample_seed: int,
                 deadlines: Optional[Dict[str, float]] = None):
        import jax
        from repro.core import scheduler
        self.sim, self.timed = sim, timed
        self.deadlines = deadlines or {}    # app id -> the trace's deadline
        self.jax = jax
        self._sched_mod = scheduler
        self._orig_delta = scheduler.refresh_ranks_delta
        self.in_window = False
        self.batch_full = False
        self.caller: Optional[str] = None   # "tick" | "event" while in a call
        self._keyed: List = []              # captures awaiting the keys
        self.rng = np.random.default_rng(sample_seed)
        self.due: set = set()               # apps due a walk
        self.credit: Dict[str, np.ndarray] = {}   # [in unit, total] float32
        self.stale = 0
        self.warming = False
        self._watched: List[str] = []
        self.reset_window()
        self._install()

    def reset_window(self):
        self.calls = 0
        self.spilled = 0
        self.rows_walked = 0
        self.tick_rows: List[int] = []
        self.event_refresh_s: List[float] = []
        self.tick_caps: List = []
        self.last_tick = None
        self.n_ticks_seen = 0
        self.event_caps: List = []
        self.n_events_seen = 0
        self.stale = 0
        self.bucket_ticks = 0

    @contextlib.contextmanager
    def span(self, name):
        if self.timed:
            with self.jax.profiler.TraceAnnotation(SPAN + name):
                yield
        else:
            yield

    def _install(self):
        sim = self.sim
        orig_refresh = sim._refresh_ranks
        layers = self

        def refresh_ranks(app_ids=None, touched=None):
            full = app_ids is None
            if full:
                layers.batch_full = True
                # a tick over no live application dispatches nothing
                layers.bucket_ticks += layers.in_window and bool(
                    layers.credit)
            layers.caller = "tick" if full else "event"
            t0 = time.perf_counter()
            try:
                with layers.span("full_tick" if full else "event_refresh"):
                    out = orig_refresh(app_ids, touched=touched)
            finally:
                layers.caller = None
            if layers.in_window and layers.timed and not full:
                layers.event_refresh_s.append(time.perf_counter() - t0)
            if layers._keyed:
                layers._read_keys()
            return out

        sim._refresh_ranks = refresh_ranks
        if self.timed:
            for attr, name in (("_apply_prewarm_plan", "prewarm_apply"),
                               ("_reschedule", "reschedule")):
                setattr(sim, attr, self._spanned(getattr(sim, attr), name))
            sim.events.next_batch = self._spanned(sim.events.next_batch,
                                                  "drain")
        self._watch_events(sim.sched)

        def refresh_ranks_delta(packed, qs, base_key, seed, **kw):
            tick = layers._orig_delta(packed, qs, base_key, seed, **kw)
            layers._walked(qs, tick, drains_all=bool(kw.get("retrigger",
                                                            True)),
                           triage=bool(kw.get("with_triage")))
            return tick

        self._sched_mod.refresh_ranks_delta = refresh_ranks_delta

    def _watch_events(self, sched):
        """Follow the scheduler's application events: a new unit, an
        admission or a requeue makes an application due a walk; progress
        is credited in float32, one addition per event."""
        due, credit = self.due, self.credit

        def wrap(name, before):
            orig = getattr(sched, name)
            self._watched.append(name)

            def fn(*a, **k):
                before(*a, **k)
                return orig(*a, **k)
            setattr(sched, name, fn)

        def arrivals(items, now):
            for item in items:
                due.add(item[0])
                credit[item[0]] = np.zeros(2, np.float32)

        def unit_start(app_id, unit, now):
            due.add(app_id)
            credit[app_id][0] = 0.0

        def unit_finish(app_id, unit, observed, now, next_unit):
            if next_unit is None:
                gone(app_id)
            else:
                unit_start(app_id, next_unit, now)

        def requeue(app_id, now):
            if app_id in credit:
                due.add(app_id)

        def progress(app_id, service_delta):
            c = credit[app_id]
            c[0] += service_delta
            c[1] += service_delta

        def gone(app_id):
            due.discard(app_id)
            credit.pop(app_id, None)

        wrap("on_arrivals", arrivals)
        wrap("on_arrival", lambda app_id, app_name, now, **k:
             arrivals([(app_id,)], now))
        wrap("on_unit_start", unit_start)
        wrap("on_unit_finish", unit_finish)
        wrap("on_requeue", requeue)
        wrap("on_progress", progress)
        wrap("on_app_complete", gone)
        wrap("on_app_shed", gone)

    def _spanned(self, fn, name):
        def wrapped(*a, **k):
            with self.span(name):
                return fn(*a, **k)
        return wrapped

    def uninstall(self):
        self._sched_mod.refresh_ranks_delta = self._orig_delta

    # -------------------------------------------------------- window count
    def _walked(self, qs, tick, *, drains_all: bool, triage: bool):
        """After one dispatch: the walked applications are no longer due;
        at a dispatch of the window that drains the whole dirty set every
        live application still due is a stale row.  The dispatch is a
        tick's or an event's as its caller was."""
        if self.warming:
            return
        ids = [qs.ids[int(s)] for s in tick.walked]
        self.due.difference_update(ids)
        if not self.in_window:
            return
        if drains_all:
            self.stale += len(self.due)
        self.calls += 1
        D = len(ids)
        self.rows_walked += D
        if tick.spill:
            self.spilled += 1
        triage = triage and D > 0
        if self.caller == "tick":
            self.tick_rows.append(D)
            # every full tick is captured: the last one is always checked
            self.last_tick = self._capture(qs, tick, ids, "tick", triage)
            self.n_ticks_seen = self._keep(self.tick_caps, self.n_ticks_seen,
                                           TICK_CAPTURES,
                                           lambda: self.last_tick)
        else:
            self.n_events_seen = self._keep(
                self.event_caps, self.n_events_seen, EVENT_CAPTURES,
                lambda: self._capture(qs, tick, ids, "event", triage))

    def _keep(self, caps: List, seen: int, k: int, capture) -> int:
        """Reservoir-sample one more dispatch into ``caps``; returns the
        count of dispatches seen."""
        slot = check.reservoir_slot(seen, k, self.rng)
        if slot is not None:
            if slot < len(caps):
                caps[slot] = capture()
            else:
                caps.append(capture())
        return seen + 1

    def _record(self, app_id: str) -> Dict:
        """An application's state as the simulator and the scheduler's
        records hold it: its unit is the simulator's trajectory position,
        its credited service the float32 sums kept here."""
        run = self.sim.sched.apps[app_id]
        app = self.sim.apps[app_id]
        executed, attained = self.credit[app_id]
        return {"app": app.inst.app_name,
                "unit": app.inst.trajectory[app.unit_idx][0],
                "executed": executed, "attained": attained,
                "key_id": run.key_id, "refresh_id": run.refreshes,
                "overrides": dict(run.overrides)}

    def _capture(self, qs, tick, ids, kind, triage: bool):
        occ = qs.occupied()
        attained = np.zeros(len(qs.ids), np.float32)
        attained[occ] = [self.credit[qs.ids[int(s)]][1] for s in occ]
        walked = np.asarray(tick.walked, np.int64)
        cap = check.Capture(kind=kind, walked=walked,
                            apps=[self._record(i) for i in ids],
                            d_probs=qs.d_probs, d_edges=qs.d_edges,
                            ranks=np.asarray(tick.ranks),
                            attained=attained, occupied=occ)
        if triage:
            # the triage the dispatch wrote into the arena's mirrors; the
            # program's class is read off its keys once the call returns
            cap.triage = np.stack([qs.sup[walked], qs.opt[walked],
                                   qs.mean[walked]])
            cap.arena_triage = np.stack([qs.sup, qs.opt]).copy()
            cap.deadline = np.full(len(qs.ids), np.inf)
            for s in occ:
                d = self.deadlines.get(qs.ids[int(s)])
                cap.deadline[s] = np.inf if d is None else d
            cap.now = float(self.sim.now)
            self._keyed.append((cap, [qs.ids[int(s)] for s in occ]))
        return cap

    def _read_keys(self):
        """The class of each live row of the captures made in the call
        that just returned: the simulator's waiting-queue key over 10**6."""
        sim = self.sim
        for cap, app_ids in self._keyed:
            cap.key_class = np.full(len(cap.attained), -1, np.int64)
            keys = sim._rank_arr[[sim._app_ai[i] for i in app_ids]]
            cap.key_class[cap.occupied] = np.floor(keys / check.CLASS_SPAN)
        self._keyed.clear()

    def warm_shapes(self, trace, rows: int) -> int:
        """Dispatch, on a copy of the scheduler and through its own
        entries, the refresh shapes the window can meet: every padded
        dirty-set size up to ``rows`` (the sizes 2**k and 2**k + 1, so any
        power-of-two padding is met) on both refresh paths.  The copy is
        topped up with fresh admissions to ``rows`` live applications
        (``rows`` is at most the arena capacity the window runs at, so the
        arena does not grow).  The sizes are swept as the state stands,
        again after each refinement, from the trace's own observations,
        that lengthens the longest override row, and last with no override
        row left.  The live scheduler is left as it was.  Returns the
        number of dispatches made."""
        import copy
        twin = copy.deepcopy(self.sim.sched)
        for name in self._watched:           # the twin's own methods
            twin.__dict__.pop(name, None)
        now = self.sim.now
        pool = [i for i in self.credit if i in twin.apps]
        names = sorted({inst.app_name for inst in trace})
        sizes = sorted({0} | {d for k in range(31)
                              for d in (1 << k, (1 << k) + 1) if d <= rows})
        made = [0]

        def sweep():
            pool[:] = [i for i in pool if not twin.apps[i].done]
            have = {twin.apps[i].app_name for i in pool}
            fresh = [f"bench-warm-{made[0]}-{k}" for k in
                     range(rows - len(pool))]
            order = sorted(set(names) - have) + names    # every graph first
            twin.on_arrivals([(i, order[k % len(order)], "bench", None)
                              for k, i in enumerate(fresh)], now)
            pool.extend(fresh)
            twin.priorities(now)          # walk what is dirty: d = 0 is 0
            for d in sizes:
                for full in (True, False):
                    for i in pool[:d]:
                        twin.on_requeue(i, now)
                    twin.priorities(now, None if full else pool[:max(d, 1)])
                    made[0] += 1

        def width(n):                     # power-of-two class of a row
            return (n - 1).bit_length() if n else -1

        def longest(app_ids):
            return max((len(v) for i in app_ids
                        for v in twin.apps[i].overrides.values()), default=0)

        self.warming = True
        try:
            sweep()
            by_graph: Dict[str, str] = {}
            for i in pool:
                by_graph.setdefault(twin.apps[i].app_name, i)
            top = longest(pool)
            for inst in trace:
                app_id = by_graph.get(inst.app_name)
                if app_id is None:
                    continue
                unit = twin.apps[app_id].current_unit
                for finished, obs in inst.trajectory:
                    twin.on_unit_finish(app_id, finished, obs, now, unit)
                n = longest([app_id])
                if width(n) > width(top):
                    sweep()
                top = max(top, n)
            if top:
                for i in pool:
                    if twin.apps[i].overrides:
                        twin.on_app_complete(i)
                sweep()
        finally:
            self.warming = False
        return made[0]

    def captures(self) -> List:
        caps = list(self.tick_caps)
        if self.last_tick is not None and \
                all(c is not self.last_tick for c in caps):
            caps.append(self.last_tick)
        return caps + list(self.event_caps)


# ------------------------------------------------------------------- run
def program_counters(sim) -> Dict[str, int]:
    """The scheduler's own counters (``refresh_stats``), as they stand;
    empty on a program without them."""
    return dict(getattr(sim.sched, "refresh_stats", {}))


def window_counters(at_open: Dict[str, int], at_close: Dict[str, int]
                    ) -> Dict[str, int]:
    """What the window added to each counter."""
    return {k: v - at_open.get(k, 0) for k, v in at_close.items()}


def build_sim(config: Dict, kb, sim_seed: int):
    from repro.core.refresh_config import RefreshConfig
    from repro.serving.simulator import ClusterSim, SimConfig
    refresh = RefreshConfig(**config.get("refresh", {}))
    return ClusterSim(kb, SimConfig(seed=sim_seed, refresh=refresh,
                                    **config["sim"]))


def run_cell(config: Dict, traffic: Dict, *, seed: int, seconds: float,
             trace: bool, chips: int = 1, require_tpu: bool = True,
             t_start: Optional[float] = None, trace_dir: Optional[Path] = None,
             control: bool = False, steer: Optional[Callable] = None,
             fault: Optional[Callable] = None, compile_cache: bool = True
             ) -> Dict:
    """One run: set-up, the window, the check.  Returns the record the
    metric readers read.  ``control`` also compares the reference at the
    lower precision.  For tests: ``steer`` is called with the simulator
    once it is built, ``fault`` just before the window opens (to break
    the timed path underneath), and ``compile_cache=False`` leaves JAX's
    persistent cache as it is."""
    t_start = time.perf_counter() if t_start is None else t_start
    info = device_info(chips, require_tpu)
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if compile_cache:
        log(f"compile cache: {enable_compile_cache()}")
    import jax
    from bench import reference, traffic_gen
    from repro.apps.suite import build_knowledge_base
    from repro.kernels.pdgraph_walk import ops
    compiles = CompileCounter()

    def phase(what):
        low, hit, miss = compiles.snapshot()
        log(f"set-up {time.perf_counter() - t_start:8.2f} s: {what} "
            f"(programs lowered {low}, cache hits {hit}, compiled {miss})")
    phase("device and imports")
    seeds = seeds_from(seed)
    kb_cfg = config["knowledge_base"]
    kb = build_knowledge_base(n_trials=int(kb_cfg["n_trials"]),
                              seed=int(kb_cfg["seed"]))
    sim_cfg = config["sim"]
    t_in = float(sim_cfg.get("t_in", 0.25e-3))
    t_out = float(sim_cfg.get("t_out", 30e-3))
    gen = traffic_gen.generate(traffic, n_llm_slots=int(sim_cfg["n_llm_slots"]),
                               t_in=t_in, t_out=t_out, seed=seeds["traffic"])
    insts = gen["instances"]
    phase("knowledge base and trace")
    log(f"trace: {len(insts)} applications, {gen['rate_per_s']:.4f} apps/s "
        f"stream (E[S] {gen['mean_demand_s']:.2f} s), last arrival "
        f"{gen['last_arrival']:.1f} s simulated")
    sim = build_sim(config, kb, seeds["sim"])
    if steer is not None:
        steer(sim)
    layers = Layers(sim, timed=trace, sample_seed=seeds["sample"],
                    deadlines={i.app_id: i.deadline for i in insts
                               if i.deadline is not None})
    warm = traffic["warmup"]
    st = {"phase": "setup", "ticks": 0, "quiet": 0,
          "lowered_at_tick": compiles.lowered, "batches": [],
          "tick_batches": [], "t_prev": 0.0}
    profiler_on = [False]

    def start_window(s):
        phase(f"warm ticks done at {s.now:.0f} s simulated, "
              f"{len(layers.credit)} live apps, {st['ticks']} ticks, the "
              f"last {st['quiet']} without a new program")
        made = layers.warm_shapes(insts, int(warm["max_rows"]))
        phase(f"warm-up: {made} dispatches on a copy of the scheduler")
        if fault is not None:
            fault(s)
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # Python calls: too many
            opts.host_tracer_level = 1        # the harness's spans
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            profiler_on[0] = True
            st["window_span"] = jax.profiler.TraceAnnotation(SPAN + "window")
            st["window_span"].__enter__()
        layers.reset_window()
        layers.in_window = True
        jax.config.update("jax_log_compiles", True)   # name what compiles
        st.update(phase="window", events0=s.events_processed,
                  policy0=s.policy_time, now0=s.now,
                  live0=len(layers.credit), compiles0=compiles.snapshot(),
                  counters0=program_counters(s))
        st["t0"] = st["t_prev"] = time.perf_counter()
        st["setup_s"] = st["t0"] - t_start

    def progress(s):
        now = time.perf_counter()
        full, layers.batch_full = layers.batch_full, False
        if st["phase"] == "window":
            dt = now - st["t_prev"]
            st["t_prev"] = now
            st["batches"].append(dt)
            if full:
                st["tick_batches"].append(dt)
            if now - st["t0"] >= seconds:
                st["t1"] = now
                st["counters1"] = program_counters(s)
                return True
            return False
        if now - t_start > SETUP_LIMIT_S:
            raise RunError(f"set-up passed {SETUP_LIMIT_S:.0f} s")
        if full:
            st["ticks"] += 1
            quiet = compiles.lowered == st["lowered_at_tick"]
            st["quiet"] = st["quiet"] + 1 if quiet else 0
            st["lowered_at_tick"] = compiles.lowered
            if st["ticks"] % 10 == 0:
                phase(f"tick at {s.now:.0f} s simulated, "
                      f"{len(layers.credit)} live apps")
            if s.now >= float(warm["min_sim_s"]) and \
                    st["quiet"] >= int(warm["quiet_ticks"]):
                start_window(s)
        return False

    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=FALLBACK_WARNING,
                                category=RuntimeWarning)
        try:
            sim.run(insts, progress=progress)
        finally:
            layers.in_window = False
            jax.config.update("jax_log_compiles", False)
            layers.uninstall()
            if profiler_on[0]:
                st["window_span"].__exit__(None, None, None)
                jax.profiler.stop_trace()
    if st["phase"] != "window" or "t1" not in st:
        raise RunError("the trace ended before the window closed "
                       f"(phase {st['phase']}, simulated {sim.now:.1f} s)")
    if sim.now >= gen["last_arrival"]:
        raise RunError(f"the window reached the end of the trace "
                       f"({sim.now:.1f} s >= {gen['last_arrival']:.1f} s)")
    window_s = st["t1"] - st["t0"]
    lowered, hits, misses = (a - b for a, b in
                             zip(compiles.snapshot(), st["compiles0"]))
    events = sim.events_processed - st["events0"]
    kernel_ok = (ops.LAST_DISPATCH == "pallas"
                 and ops.LAST_INTERPRET is False)
    mem = jax.devices()[0].memory_stats() or {}
    rec = {
        "device": dict(info, memory_peak_bytes=int(
            mem.get("peak_bytes_in_use", 0))),
        "setup_s": st["setup_s"], "window_s": window_s,
        "events": events, "batches": np.asarray(st["batches"]),
        "tick_batches": np.asarray(st["tick_batches"]),
        "policy_s": sim.policy_time - st["policy0"],
        "event_refresh_s": np.asarray(layers.event_refresh_s),
        "tick_rows": np.asarray(layers.tick_rows),
        "rows_walked": layers.rows_walked,
        "bucket_ticks": layers.bucket_ticks,
        "counters": window_counters(st["counters0"], st["counters1"]),
        "attempted": layers.calls, "spilled": layers.spilled,
        "kernel_ok": kernel_ok,
        "compiles": {"lowered": lowered, "cache_hits": hits,
                     "compiled": misses},
        "sim_s": sim.now - st["now0"], "live0": st["live0"],
        "live1": len(layers.credit), "capacity": getattr(
            getattr(sim.sched, "_qstate", None), "capacity", None),
        "spill_total": sim.sched.fused_spill,
        "last_dispatch": (ops.LAST_DISPATCH, ops.LAST_INTERPRET),
        "trace": None,
    }
    log(f"window: {window_s:.3f} s wall, {rec['sim_s']:.1f} s simulated "
        f"(real-time factor {rec['sim_s'] / window_s:.3f}), live apps "
        f"{rec['live0']} -> {rec['live1']} at capacity {rec['capacity']}")
    log(f"window: {len(rec['batches'])} micro-batches, "
        f"{len(rec['tick_batches'])} with a full tick, {events} events, "
        f"{rec['attempted']} refresh dispatches, {rec['rows_walked']} rows "
        "walked")
    log(f"window: {rec['bucket_ticks']} bucket ticks over live "
        f"applications, {len(rec['tick_rows'])} tick dispatches; program "
        f"counters {json.dumps(rec['counters'])}")
    log(f"window compiles: lowered={lowered} cache_hits={hits} "
        f"compiled={misses}")
    log(f"kernel: LAST_DISPATCH={ops.LAST_DISPATCH!r} "
        f"interpret={ops.LAST_INTERPRET}; spill: {sim.sched.fused_spill} "
        f"({layers.spilled} dispatches spilled in the window)")
    log(f"set-up: {rec['setup_s']:.3f} s")
    if trace:
        from bench import trace_reduce
        rec["trace"] = trace_reduce.reduce_dir(trace_dir)
    # --- the check, after the window, the device memory read
    caps = layers.captures()
    tables = reference.build_tables(kb, t_in, t_out)
    n_w, n_b = int(sim_cfg["mc_walkers"]), int(sim_cfg["n_buckets"])
    t_chk = time.perf_counter()
    parts = [check.compare_capture(c, tables, seed=seeds["sim"],
                                   n_walkers=n_w, n_buckets=n_b)
             for c in caps]
    numbers = check.combine(parts)
    numbers["stale_rows"] = float(layers.stale)
    numbers["dispatch_violations"] = float(check.dispatch_violations(sim))
    rec["check"] = numbers
    worst = max(parts, key=lambda p: p["rank_gap"], default=None)
    if worst is not None and worst["rank_gap"] > 0:
        log(f"check: widest rank gap at {json.dumps(worst['worst_rank_row'])}")
    rec["check_s"] = time.perf_counter() - t_chk
    rec["checked"] = {"dispatches": len(caps),
                      "rows": sum(p["rows"] for p in parts),
                      "arena_rows": sum(p["arena_rows"] for p in parts)}
    if control:
        ctl = [check.compare_capture(c, tables, seed=seeds["sim"],
                                     n_walkers=n_w, n_buckets=n_b,
                                     precision="high") for c in caps]
        rec["control"] = check.combine(ctl)
    limits = load_limits()
    rec["correct"] = bool(check.verdict(numbers, limits))
    rec["limits"] = limits
    log(f"check: {len(caps)} dispatches, {rec['checked']['rows']} walked "
        f"rows, {rec['checked']['arena_rows']} arena rows, "
        f"{rec['check_s']:.3f} s")
    return rec


def result_line(rec: Dict, names: List[str], units: Dict[str, str],
                trace: bool) -> Dict:
    metrics = {}
    for name in names:
        v = metric_reader(name)(rec)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": units[name]}
    device = dict(rec["device"])
    out = {"correct": rec["correct"], "attempted": rec["attempted"],
           "failed": rec["spilled"], "metrics": metrics, "device": device}
    if trace and rec["trace"] is not None:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        out["breakdown"] = rec["trace"]["breakdown"]
    out["checks"] = {k: {"value": rec["check"][k], "limit": rec["limits"][k]}
                     for k in check.NUMBERS}
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise RunError(f"no program under {ROOT / 'src'}: run from a "
                           "checkout of the repository")
        sys.path.insert(0, str(ROOT / "src"))
        bm = load_benchmark()
        cell, config, traffic, e2e, layer, units = cell_parts(
            bm, args.workload)
        trace_dir = ROOT / ".bench_trace" / args.workload
        if args.trace:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
        rec = run_cell(config, traffic, seed=args.seed,
                       seconds=args.seconds, trace=bool(args.trace),
                       chips=int(cell["chips"]), t_start=t_start,
                       trace_dir=trace_dir)
        if not rec["kernel_ok"]:
            raise RunError("the window's refresh did not run the compiled "
                           f"kernel: {rec['last_dispatch']}")
        if args.trace:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
    except RunError as e:
        log(f"FAILED: {e}")
        return 1
    line = result_line(rec, layer if args.trace else e2e, units,
                       bool(args.trace))
    log(f"check walker_moves (not compared): {rec['check']['walker_moves']!r}")
    for k, v in line["checks"].items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0
