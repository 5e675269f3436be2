"""Probabilistic Demand Graph (PDGraph) — the paper's demand model (§3.2).

Each *functional unit* records:
  backend-spec        which backend the unit runs on (LLM model [+LoRA,
                      +prefix-cache id], docker image, or DNN tool)
  backend-consumption empirical sample lists — input/output token lengths and
                      request parallelism for LLM units, wall duration for
                      non-LLM units.  Raw values are kept (the paper found raw
                      lists beat fitted skew-normal coefficients), FIFO-capped
                      at 1000 entries.
  next-unit           branch-taking probabilities from historical frequencies.

Per-trial records are kept (not just per-unit marginals) so that online
refinement can *join* upstream and downstream observations of the same trial
and filter on the observed buckets (§3.2 "online estimation refinement").

Total-demand estimation is a vectorized Monte-Carlo random walk over the
graph, jit-compiled (`mc_service_samples`) — this is the scheduler hot path
whose runtime the paper reports in Fig. 15.

For cluster-scale queues the per-application walk is also available as a
single batched dispatch: ``pack_graphs`` pads every PDGraph in the knowledge
base into shared ``(G, U, S)`` unit tables and ``mc_service_samples_batch``
runs one jitted vmapped walker over the whole queue (per-app start unit,
attained service, and conditional-refinement sample overrides included), so
the refresh tick costs one XLA dispatch instead of one per application.
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

MAX_SAMPLES = 1000  # FIFO cap per the paper
N_BUCKETS = 10


@dataclass(frozen=True)
class BackendSpec:
    kind: str                 # "llm" | "docker" | "dnn"
    model: str = ""           # LLM name / docker image / DNN tool name
    lora: str = ""            # optional LoRA adapter id
    prefix: str = ""          # shared-system-prompt id (KV prefix cache key)

    def resource_keys(self) -> Tuple[str, ...]:
        """Identities of the warmable backend contents this unit needs."""
        if self.kind == "llm":
            keys = []
            if self.lora:
                keys.append(f"lora:{self.lora}")
            if self.prefix:
                keys.append(f"kv:{self.prefix}")
            return tuple(keys)
        return (f"{self.kind}:{self.model}",)

    def resource_key(self) -> str:
        keys = self.resource_keys()
        return keys[0] if keys else f"llm:{self.model}"


@dataclass
class UnitNode:
    name: str
    backend: BackendSpec
    input_len: List[float] = field(default_factory=list)
    output_len: List[float] = field(default_factory=list)
    parallelism: List[float] = field(default_factory=list)
    duration: List[float] = field(default_factory=list)   # non-LLM wall time
    next_counts: Dict[str, int] = field(default_factory=dict)  # incl. "$end"
    corr_mask: Dict[str, bool] = field(default_factory=dict)

    def next_probs(self) -> Dict[str, float]:
        tot = sum(self.next_counts.values())
        if not tot:
            return {"$end": 1.0}
        return {k: v / tot for k, v in self.next_counts.items()}

    def service_samples(self, t_in: float, t_out: float) -> np.ndarray:
        """Per-trial unit service demand in seconds (LLM: parallelism *
        (in*t_in + out*t_out); non-LLM: recorded duration)."""
        if self.backend.kind == "llm":
            i = np.asarray(self.input_len, np.float64)
            o = np.asarray(self.output_len, np.float64)
            p = np.asarray(self.parallelism, np.float64)
            n = min(len(i), len(o), len(p))
            if n == 0:
                return np.asarray([1.0])
            return p[:n] * (i[:n] * t_in + o[:n] * t_out)
        d = np.asarray(self.duration, np.float64)
        return d if len(d) else np.asarray([1.0])


def _fifo(lst: List, x) -> None:
    lst.append(float(x))
    if len(lst) > MAX_SAMPLES:
        del lst[0]


class PDGraph:
    """Knowledge-base entry for one application."""

    def __init__(self, app_name: str, entry: str,
                 units: Optional[Dict[str, UnitNode]] = None):
        self.app_name = app_name
        self.entry = entry
        self.units: Dict[str, UnitNode] = units or {}
        # per-trial joined records for correlation / conditional refinement:
        # trials[i][unit_name] = {"in":..,"out":..,"par":..,"dur":..}
        self.trials: List[Dict[str, Dict[str, float]]] = []
        self._compiled = None
        self.version = 0          # bumped on every record_trial (pack caches)

    # ------------------------------------------------------------ recording
    def record_trial(self, trace: Sequence[Tuple[str, Dict[str, float]]]) -> None:
        """trace: ordered [(unit_name, {"in","out","par","dur"}), ...]."""
        rec: Dict[str, Dict[str, float]] = {}
        prev: Optional[str] = None
        for name, obs in trace:
            u = self.units[name]
            if u.backend.kind == "llm":
                _fifo(u.input_len, obs.get("in", 0))
                _fifo(u.output_len, obs.get("out", 0))
                _fifo(u.parallelism, obs.get("par", 1))
            else:
                _fifo(u.duration, obs.get("dur", 0))
            if prev is not None:
                self.units[prev].next_counts[name] = \
                    self.units[prev].next_counts.get(name, 0) + 1
            rec[name] = dict(obs)
            prev = name
        if prev is not None:
            self.units[prev].next_counts["$end"] = \
                self.units[prev].next_counts.get("$end", 0) + 1
        self.trials.append(rec)
        if len(self.trials) > MAX_SAMPLES:
            del self.trials[0]
        self._compiled = None
        self.version += 1

    # ----------------------------------------------------------- compilation
    def compile_arrays(self, t_in: float, t_out: float):
        """Pack the graph into dense arrays for the jitted MC walker."""
        if self._compiled is not None and self._compiled[0] == (t_in, t_out):
            return self._compiled[1]
        names = sorted(self.units)
        idx = {n: i for i, n in enumerate(names)}
        U = len(names)
        S = max(max((len(self.units[n].service_samples(t_in, t_out))
                     for n in names), default=1), 1)
        samples = np.zeros((U, S), np.float32)
        counts = np.zeros((U,), np.int32)
        cum_trans = np.zeros((U, U + 1), np.float32)
        for n in names:
            u = self.units[n]
            sv = u.service_samples(t_in, t_out)
            counts[idx[n]] = len(sv)
            samples[idx[n], :len(sv)] = sv
            probs = np.zeros(U + 1, np.float32)
            for tgt, pr in u.next_probs().items():
                probs[U if tgt == "$end" else idx[tgt]] = pr
            cum_trans[idx[n]] = np.cumsum(probs)
        packed = {
            "names": names, "index": idx,
            "samples": jnp.asarray(samples), "counts": jnp.asarray(counts),
            "cum_trans": jnp.asarray(cum_trans), "entry": idx[self.entry],
        }
        self._compiled = ((t_in, t_out), packed)
        return packed

    # ------------------------------------------------------------- sampling
    def mc_service_samples(self, key, t_in: float, t_out: float,
                           start_unit: Optional[str] = None,
                           executed_in_unit: float = 0.0,
                           unit_sample_override: Optional[Dict[str, np.ndarray]] = None,
                           n_walkers: int = 512,
                           max_steps: int = 64) -> np.ndarray:
        """Remaining-service-time samples from `start_unit` (default: entry).

        `unit_sample_override` replaces a unit's demand samples (the online
        conditional refinement hook).  `executed_in_unit` subtracts attained
        service inside the current unit (floored at 0 per walker).
        """
        packed = self.compile_arrays(t_in, t_out)
        samples, counts = packed["samples"], packed["counts"]
        if unit_sample_override:
            samples = np.array(samples)
            counts = np.array(counts)
            for name, arr in unit_sample_override.items():
                i = packed["index"][name]
                arr = np.asarray(arr, np.float32)[:samples.shape[1]]
                if len(arr) == 0:
                    continue
                samples[i, :len(arr)] = arr
                counts[i] = len(arr)
            samples, counts = jnp.asarray(samples), jnp.asarray(counts)
        start = packed["index"][start_unit] if start_unit else packed["entry"]
        out = _mc_walk(samples, counts, packed["cum_trans"],
                       jnp.asarray(start, jnp.int32),
                       jnp.asarray(executed_in_unit, jnp.float32),
                       key, n_walkers, max_steps)
        return np.asarray(out)

    # --------------------------------------------------------------- (de)ser
    def to_json(self) -> str:
        d = {
            "app_name": self.app_name, "entry": self.entry,
            "units": {n: {
                "backend": dataclasses.asdict(u.backend),
                "input_len": u.input_len, "output_len": u.output_len,
                "parallelism": u.parallelism, "duration": u.duration,
                "next_counts": u.next_counts, "corr_mask": u.corr_mask,
            } for n, u in self.units.items()},
            "trials": self.trials,
        }
        return json.dumps(d)

    @classmethod
    def from_json(cls, s: str) -> "PDGraph":
        d = json.loads(s)
        units = {}
        for n, ud in d["units"].items():
            units[n] = UnitNode(
                name=n, backend=BackendSpec(**ud["backend"]),
                input_len=ud["input_len"], output_len=ud["output_len"],
                parallelism=ud["parallelism"], duration=ud["duration"],
                next_counts={k: int(v) for k, v in ud["next_counts"].items()},
                corr_mask=ud.get("corr_mask", {}))
        g = cls(d["app_name"], d["entry"], units)
        g.trials = d.get("trials", [])
        return g


def _as_typed_key(key):
    """Accept legacy uint32 PRNGKey arrays and new-style typed keys alike.

    Typed scalar keys trace to measurably faster threefry code on CPU than
    raw (2,)-uint32 key arrays, and the bits are identical."""
    if jnp.issubdtype(getattr(key, "dtype", None), jax.dtypes.prng_key):
        return key
    return jax.random.wrap_key_data(jnp.asarray(key, jnp.uint32))


ARRIVAL_NEVER = 1e30   # first-arrival sentinel: unit never reached


def _walk_core(samples, counts, cum_trans, ov_samples, ov_counts,
               start, executed, key, n_walkers: int, max_steps: int,
               track_arrivals: bool = False,
               po_cum=None, po_scale=None):
    """Single-application random walk over (U,S) unit tables.

    ``ov_samples (U,So)`` / ``ov_counts (U,)`` carry online-refinement sample
    overrides: a unit with ov_counts > 0 draws from its override row instead
    of the base table.  Absorbing state is U (= cum_trans.shape[1] - 1).

    With ``track_arrivals`` the walk also records, per walker and unit, the
    cumulative service at the walker's FIRST entry into that unit
    (``ARRIVAL_NEVER`` where never entered) and returns ``(total, arrivals)``.
    The uniform stream is drawn identically either way, so the returned
    totals are bit-identical with tracking on or off — the prewarm planner
    rides the rank walk for free.

    ``po_cum (U, U+1)`` / ``po_scale (U,)`` switch on posterior sampling
    (``repro.core.posterior``): transitions draw against the
    posterior-blended CDF instead of ``cum_trans`` and every sampled service
    draw is rescaled by the unit's posterior-to-prior demand-mean ratio.
    Both tables arrive pre-blended (zero-observation units carry the prior
    CDF bitwise and a scale of exactly 1.0), and the uniform stream does not
    depend on them — ``None`` leaves the trace untouched."""
    U = cum_trans.shape[1] - 1
    unit_ids = jnp.arange(U, dtype=jnp.int32)
    trans_cdf = cum_trans if po_cum is None else po_cum

    def step(carry, k):
        cur, total, done, first, arr = carry
        # one key per step: demand and transition uniforms come from a
        # single threefry call (halves the RNG work on the tick hot path)
        u = jax.random.uniform(k, (2, n_walkers))
        r, r2 = u[0], u[1]
        # sample unit demand (override row wins when present)
        n_eff = jnp.where(ov_counts[cur] > 0, ov_counts[cur], counts[cur])
        sidx = jnp.floor(r * n_eff).astype(jnp.int32)
        svc = jnp.where(ov_counts[cur] > 0,
                        ov_samples[cur, jnp.minimum(sidx, ov_samples.shape[1] - 1)],
                        samples[cur, sidx])
        if po_scale is not None:
            svc = svc * po_scale[cur]
        svc = jnp.where(first, jnp.maximum(svc - executed, 0.0), svc)
        total = total + jnp.where(done, 0.0, svc)
        # sample transition
        nxt = jnp.sum(r2[:, None] > trans_cdf[cur], axis=-1).astype(jnp.int32)
        nxt = jnp.minimum(nxt, U)
        new_done = done | (nxt >= U)
        if track_arrivals:
            # walker enters `nxt` when the current unit's service completes,
            # i.e. at the just-updated total; min keeps the first entry
            enter = (~done) & (nxt < U)
            onehot = enter[:, None] & (nxt[:, None] == unit_ids[None, :])
            arr = jnp.where(onehot, jnp.minimum(arr, total[:, None]), arr)
        cur = jnp.where(new_done, cur, nxt)
        return (cur, total, new_done, jnp.zeros_like(first), arr), None

    keys = jax.random.split(key, max_steps)
    arr0 = (jnp.full((n_walkers, U), ARRIVAL_NEVER, jnp.float32)
            if track_arrivals else jnp.zeros((n_walkers, 0), jnp.float32))
    init = (jnp.full((n_walkers,), start, jnp.int32),
            jnp.zeros((n_walkers,), jnp.float32),
            jnp.zeros((n_walkers,), bool),
            jnp.ones((n_walkers,), bool),
            arr0)
    # unroll: XLA-CPU scan pays per-iteration overhead comparable to this
    # small step body; 4x unrolling is ~40% faster at cluster-scale batches
    (cur, total, done, _, arr), _ = jax.lax.scan(step, init, keys, unroll=4)
    return (total, arr) if track_arrivals else total


@partial(jax.jit, static_argnames=("n_walkers", "max_steps"))
def _mc_walk(samples: jnp.ndarray, counts: jnp.ndarray, cum_trans: jnp.ndarray,
             start: jnp.ndarray, executed: jnp.ndarray, key,
             n_walkers: int, max_steps: int) -> jnp.ndarray:
    """Vectorized random walk: (U,S) demand samples, (U,U+1) cumulative
    transition probs, absorbing state U.  Returns (n_walkers,) remaining
    service times."""
    no_ov = jnp.zeros((samples.shape[0], 1), samples.dtype)
    no_ovc = jnp.zeros((samples.shape[0],), jnp.int32)
    return _walk_core(samples, counts, cum_trans, no_ov, no_ovc,
                      start, executed, _as_typed_key(key),
                      n_walkers, max_steps)


# --------------------------------------------------------------------------
# Whole-queue batched sampling (the Fig. 15 refresh-tick hot path at scale)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class PackedKB:
    """Every PDGraph in a knowledge base padded into shared unit tables."""
    names: Tuple[str, ...]                 # graph order
    graph_index: Dict[str, int]            # app_name -> graph row
    unit_index: Tuple[Dict[str, int], ...]  # per graph: unit name -> local idx
    entry: np.ndarray                      # (G,) int32 entry-unit index
    samples: jnp.ndarray                   # (G, U, S) float32
    counts: jnp.ndarray                    # (G, U) int32
    cum_trans: jnp.ndarray                 # (G, U, U+1) float32

    @property
    def n_units(self) -> int:
        return self.samples.shape[1]

    @property
    def n_samples(self) -> int:
        return self.samples.shape[2]


def pack_graphs(graphs: Dict[str, PDGraph], t_in: float, t_out: float
                ) -> PackedKB:
    """Pad all graphs' compiled arrays to a common (U, S) so one jitted
    walker serves the whole knowledge base.  Padding units absorb on their
    first transition (end-probability 1, zero service), so walkers can never
    pick up demand from another graph's rows."""
    names = tuple(sorted(graphs))
    packs = [graphs[n].compile_arrays(t_in, t_out) for n in names]
    G = len(names)
    U = max((p["cum_trans"].shape[0] for p in packs), default=1)
    S = max((p["samples"].shape[1] for p in packs), default=1)
    samples = np.zeros((G, U, S), np.float32)
    counts = np.ones((G, U), np.int32)
    cum = np.zeros((G, U, U + 1), np.float32)
    cum[:, :, -1] = 1.0                     # pad rows: absorb immediately
    entry = np.zeros((G,), np.int32)
    for g, p in enumerate(packs):
        Ug = p["cum_trans"].shape[0]
        sg = np.asarray(p["samples"])
        samples[g, :Ug, :sg.shape[1]] = sg
        counts[g, :Ug] = np.asarray(p["counts"])
        cg = np.asarray(p["cum_trans"])     # (Ug, Ug+1) cumulative
        probs = np.diff(np.concatenate(
            [np.zeros((Ug, 1), np.float32), cg], axis=1), axis=1)
        padded = np.zeros((Ug, U + 1), np.float32)
        padded[:, :Ug] = probs[:, :Ug]      # real targets keep local indices
        padded[:, U] = probs[:, Ug]         # "$end" moves to the shared sink
        cum[g, :Ug] = np.cumsum(padded, axis=1)
        entry[g] = int(p["entry"])
    return PackedKB(names=names,
                    graph_index={n: i for i, n in enumerate(names)},
                    unit_index=tuple(p["index"] for p in packs),
                    entry=entry,
                    samples=jnp.asarray(samples),
                    counts=jnp.asarray(counts),
                    cum_trans=jnp.asarray(cum))


@partial(jax.jit, static_argnames=("n_walkers", "max_steps",
                                   "track_arrivals"))
def _mc_walk_batch(samples, counts, cum_trans,          # (G,U,S),(G,U),(G,U,U+1)
                   graph_idx, start, executed,          # (A,) each
                   base_key, key_ids, refresh_ids,      # key, (A,), (A,)
                   ov_samples, ov_counts,               # (A,U,So), (A,U)
                   n_walkers: int, max_steps: int,
                   track_arrivals: bool = False,
                   po_cum=None, po_scale=None) -> jnp.ndarray:
    """One dispatch for the whole queue: vmap of `_walk_core` with per-app
    graph gather and per-app fold_in keys (identical bits to the
    single-app walk, which derives the same fold_in chain).  With
    ``track_arrivals`` returns ``(totals (A,W), arrivals (A,W,U))``.

    ``po_cum (A, U, U+1)`` / ``po_scale (A, U)`` (posterior-blended walk
    tables, see ``repro.core.posterior``) switch on per-app posterior
    sampling; ``None`` (the default) keeps the frozen-prior trace
    bit-identical — the keyword defaults don't even enter the jit cache
    key."""
    base_key = _as_typed_key(base_key)

    if po_cum is None:
        def one(g, st, ex, kid, rid, ovs, ovc):
            key = jax.random.fold_in(jax.random.fold_in(base_key, kid), rid)
            return _walk_core(samples[g], counts[g], cum_trans[g], ovs, ovc,
                              st, ex, key, n_walkers, max_steps,
                              track_arrivals=track_arrivals)

        return jax.vmap(one)(graph_idx, start, executed,
                             key_ids, refresh_ids, ov_samples, ov_counts)

    def one_po(g, st, ex, kid, rid, ovs, ovc, pc, ps):
        key = jax.random.fold_in(jax.random.fold_in(base_key, kid), rid)
        return _walk_core(samples[g], counts[g], cum_trans[g], ovs, ovc,
                          st, ex, key, n_walkers, max_steps,
                          track_arrivals=track_arrivals,
                          po_cum=pc, po_scale=ps)

    return jax.vmap(one_po)(graph_idx, start, executed,
                            key_ids, refresh_ids, ov_samples, ov_counts,
                            po_cum, po_scale)


def _pow2_ceil(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def mc_service_samples_batch(
        packed: PackedKB, base_key, *,
        graph_idx: np.ndarray, start: np.ndarray, executed: np.ndarray,
        key_ids: np.ndarray, refresh_ids: np.ndarray,
        overrides: Optional[Sequence[Optional[Dict[str, np.ndarray]]]] = None,
        n_walkers: int = 512, max_steps: int = 64) -> np.ndarray:
    """Remaining-service samples for A applications in one jitted dispatch.

    ``overrides[a]`` maps unit name -> conditional sample array (the online
    refinement hook); rows are padded and the batch is padded to a power of
    two so jit caches stay small across queue sizes.  Returns (A, n_walkers).
    """
    A = len(graph_idx)
    if A == 0:
        return np.zeros((0, n_walkers), np.float32)
    U, S = packed.n_units, packed.n_samples
    So = 1
    if overrides:
        for ov in overrides:
            for arr in (ov or {}).values():
                So = max(So, min(len(arr), S))
        So = min(_pow2_ceil(So), S) if So > 1 else 1
    Ap = _pow2_ceil(A)
    gi = np.zeros((Ap,), np.int32)
    st = np.zeros((Ap,), np.int32)
    ex = np.zeros((Ap,), np.float32)
    kid = np.zeros((Ap,), np.int32)
    rid = np.zeros((Ap,), np.int32)
    gi[:A] = np.asarray(graph_idx, np.int32)
    st[:A] = np.asarray(start, np.int32)
    st[A:] = packed.entry[0]
    ex[:A] = np.asarray(executed, np.float32)
    kid[:A] = np.asarray(key_ids, np.int32)
    rid[:A] = np.asarray(refresh_ids, np.int32)
    ovs = np.zeros((Ap, U, So), np.float32)
    ovc = np.zeros((Ap, U), np.int32)
    if overrides:
        for a, ov in enumerate(overrides):
            if not ov:
                continue
            uidx = packed.unit_index[int(gi[a])]
            for name, arr in ov.items():
                if name not in uidx:
                    continue
                arr = np.asarray(arr, np.float32)[:So]
                if len(arr) == 0:
                    continue
                i = uidx[name]
                ovs[a, i, :len(arr)] = arr
                ovc[a, i] = len(arr)
    out = _mc_walk_batch(packed.samples, packed.counts, packed.cum_trans,
                         jnp.asarray(gi), jnp.asarray(st), jnp.asarray(ex),
                         base_key, jnp.asarray(kid), jnp.asarray(rid),
                         jnp.asarray(ovs), jnp.asarray(ovc),
                         n_walkers, max_steps)
    return np.asarray(out)[:A]
