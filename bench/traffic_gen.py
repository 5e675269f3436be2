"""The benchmark's traffic generator: one general generator, driven by the
parameters of a traffic file (``bench/traffic/<name>.json``).

The open-arrival part is a copy of ``repro.apps.workload``'s
``open_arrivals`` / ``mean_service_demand`` / ``make_open_workload`` and of
``repro.apps.suite.sample_app_names``, kept here so that no change to the
program moves the yardstick.  A test checks that, for the same seed, the
copy still yields the same arrival times, app names, tenants and
trajectories as the program's ``make_open_workload``.  Trajectories are
drawn from the application suite itself (``repro.apps.suite.SUITE`` via
``repro.apps.spec.sample_trajectory``): the suite is what the cluster
serves, not part of the generator.

A traffic file holds::

    {"stream": {"process": "gamma", "cv": 2.5, "tenants": 16,
                "load": 1.0, "duration_s": 900.0},
     "burst": {"n_apps": 12288, "waves": 1, "span_s": 1.0},   # optional
     "deadlines": {"scales": [1.2, 1.5, 2.0], "share": 1.0},  # optional
     "demand_probe": {"n_probe": 2000, "seed": 0}}

``set_seed`` (optional, in ``stream``) fixes the stream for every run
seed: its arrival times and its set of applications (names, tenants,
trajectories) come from that seed, and the run seed only shuffles which
application arrives at which time.  Every run then offers the same
arrivals and the same work, in another order.

``load`` is the offered load rho = lambda * E[S] / LLM slots, solved
against the configuration's LLM pool.  E[S] comes from a probe with a
seed fixed in the file, so every run seed offers the same rate; the run
seed draws the arrival times, names, tenants and trajectories.  The burst
is a flash crowd of ``n_apps`` arrivals split evenly into ``waves``
same-instant waves over the first ``span_s`` simulated seconds (each wave
is admitted as one batch through the simulator's arrival path).

``deadlines`` gives a share of the stream's applications a deadline, as
``make_open_workload(with_deadlines=True)`` does (arXiv 2506.14851 Fig. 11):
``arrival + scale * (true service + cold-start overhead)``, with the scale
drawn uniformly from ``scales``.  Under ``set_seed`` each application keeps
its ``scale * (service + overhead)`` and takes it to its new arrival time.
Without the section no application carries a deadline, and the trace is
the one the file gave before the section existed.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

# §5.1 size mix: 72% small / 26% medium / 2% large (LLMR excluded, per paper)
MIX = {
    "small": (["EV", "FEV", "CC", "ALFWI", "KBQAV"], 0.72),
    "medium": (["CG", "PE"], 0.26),
    "large": (["DM", "MRS"], 0.02),
}


def sample_app_names(n: int, rng: np.random.Generator) -> List[str]:
    names, probs = [], []
    for _cls, (apps, p) in MIX.items():
        for x in apps:
            names.append(x)
            probs.append(p / len(apps))
    probs = np.asarray(probs) / np.sum(probs)
    return [names[i] for i in rng.choice(len(names), size=n, p=probs)]


def open_arrivals(rate_per_s: float, duration_s: float,
                  rng: np.random.Generator, *,
                  process: str = "poisson", cv: float = 2.0) -> np.ndarray:
    """Arrival times of an open-loop renewal process on [0, duration)."""
    if rate_per_s <= 0 or duration_s <= 0:
        return np.zeros(0)
    if process == "gamma" and cv <= 0:
        raise ValueError(f"gamma arrivals need cv > 0, got {cv}")
    mean_gap = 1.0 / rate_per_s
    out, t = [], 0.0
    chunk = max(int(rate_per_s * duration_s * 1.25) + 16, 64)
    while t < duration_s:
        if process == "poisson":
            gaps = rng.exponential(mean_gap, chunk)
        elif process == "gamma":
            shape = 1.0 / (cv * cv)
            gaps = rng.gamma(shape, mean_gap / shape, chunk)
        else:
            raise ValueError(f"unknown arrival process {process!r}")
        times = t + np.cumsum(gaps)
        out.append(times[times < duration_s])
        t = float(times[-1])
    return np.concatenate(out) if out else np.zeros(0)


def mean_service_demand(suite, *, t_in: float, t_out: float,
                        n_probe: int = 200, seed: int = 0) -> float:
    """Monte-Carlo E[service seconds] per application under the §5.1 mix,
    cold starts included: the lambda * E[S] side of the load equation."""
    from repro.apps.spec import coldstart_overhead, sample_trajectory, \
        trajectory_service
    rng = np.random.default_rng(seed)
    names = sample_app_names(n_probe, rng)
    tot = 0.0
    for name in names:
        traj = sample_trajectory(suite[name], rng)
        tot += trajectory_service(traj, t_in, t_out) \
            + coldstart_overhead(suite[name], traj)
    return tot / max(n_probe, 1)


def open_workload(duration_s: float, *, t_in: float, t_out: float,
                  rate_per_s: Optional[float] = None,
                  target_load: Optional[float] = None,
                  n_service_slots: int = 16, process: str = "poisson",
                  cv: float = 2.0, tenants: int = 8, seed: int = 0,
                  max_apps: Optional[int] = None, suite=None,
                  deadlines: Optional[Dict] = None):
    """Copy of ``make_open_workload`` (uniform tenants, global mix): the
    renewal process, then per-arrival tenant, name, deadline and
    trajectory draws in the program's order.  Returns the instances and
    each one's deadline budget ``scale * (service + overhead)`` (None
    where it has no deadline)."""
    from repro.apps.spec import coldstart_overhead, sample_trajectory, \
        trajectory_service
    from repro.apps.suite import SUITE
    from repro.apps.workload import AppInstance
    if (rate_per_s is None) == (target_load is None):
        raise ValueError("give exactly one of rate_per_s / target_load")
    rng = np.random.default_rng(seed)
    suite = suite or SUITE
    if rate_per_s is None:
        e_s = mean_service_demand(suite, t_in=t_in, t_out=t_out, seed=seed)
        rate_per_s = target_load * n_service_slots / max(e_s, 1e-9)
    times = open_arrivals(rate_per_s, duration_s, rng, process=process,
                          cv=cv)
    if max_apps is not None:
        times = times[:max_apps]
    n_ten = max(tenants, 1)
    weights = np.ones(n_ten, np.float64)
    weights = weights / weights.sum()
    n = len(times)
    prof_idx = (rng.choice(n_ten, size=n, p=weights)
                if n else np.zeros(0, np.int64))
    names = sample_app_names(n, rng) if n else []
    has_ddl = np.zeros(n, bool)
    if deadlines and n:
        scales = [float(x) for x in deadlines["scales"]]
        has_ddl = rng.uniform(size=n) < float(deadlines.get("share", 1.0))
        ddl_pick = rng.integers(len(scales), size=n)
    insts, budgets = [], []
    for i, (t, name) in enumerate(zip(times, names)):
        traj = sample_trajectory(suite[name], rng)
        budget = None
        if has_ddl[i]:
            budget = scales[int(ddl_pick[i])] * (
                trajectory_service(traj, t_in, t_out)
                + coldstart_overhead(suite[name], traj))
        insts.append(AppInstance(
            app_id=f"app{i:06d}", app_name=name,
            tenant=f"tenant{prof_idx[i]}", arrival=float(t), trajectory=traj,
            deadline=None if budget is None else float(t + budget)))
        budgets.append(budget)
    return insts, budgets


def burst_workload(n_apps: int, waves: int, span_s: float, *, tenants: int,
                   seed: int, suite=None):
    """A flash crowd: ``n_apps`` arrivals in ``waves`` equal same-instant
    waves at the midpoints of ``span_s / waves`` intervals."""
    from repro.apps.spec import sample_trajectory
    from repro.apps.suite import SUITE
    from repro.apps.workload import AppInstance
    suite = suite or SUITE
    rng = np.random.default_rng(seed)
    step = span_s / waves
    wave = np.arange(n_apps) * waves // n_apps
    tenant = rng.integers(max(tenants, 1), size=n_apps)
    names = sample_app_names(n_apps, rng)
    return [AppInstance(app_id=f"crowd{i:06d}", app_name=name,
                        tenant=f"tenant{tenant[i]}",
                        arrival=float((wave[i] + 0.5) * step),
                        trajectory=sample_trajectory(suite[name], rng))
            for i, name in enumerate(names)]


def generate(traffic: Dict, *, n_llm_slots: int, t_in: float, t_out: float,
             seed: int) -> Dict:
    """The whole trace of one run: ``{"instances", "rate_per_s",
    "mean_demand_s", "last_arrival"}``, ordered by arrival."""
    from repro.apps.suite import SUITE
    st = traffic["stream"]
    probe = traffic.get("demand_probe", {"n_probe": 200, "seed": 0})
    e_s = mean_service_demand(SUITE, t_in=t_in, t_out=t_out,
                              n_probe=int(probe["n_probe"]),
                              seed=int(probe["seed"]))
    rate = float(st["load"]) * n_llm_slots / e_s
    ss = np.random.SeedSequence(seed).generate_state(3)
    fixed = st.get("set_seed")
    insts, budgets = open_workload(
        float(st["duration_s"]), t_in=t_in, t_out=t_out, rate_per_s=rate,
        process=st["process"], cv=float(st["cv"]),
        tenants=int(st["tenants"]),
        seed=int(ss[0]) if fixed is None else int(fixed),
        deadlines=traffic.get("deadlines"))
    if fixed is not None:
        order = np.random.default_rng(int(ss[2])).permutation(len(insts))
        insts = [dataclasses.replace(
            insts[j], arrival=insts[i].arrival, app_id=insts[i].app_id,
            deadline=None if budgets[j] is None
            else float(insts[i].arrival + budgets[j]))
            for i, j in enumerate(order)]
    b = traffic.get("burst")
    if b:
        insts = insts + burst_workload(int(b["n_apps"]), int(b["waves"]),
                                       float(b["span_s"]),
                                       tenants=int(st["tenants"]),
                                       seed=int(ss[1]))
        insts.sort(key=lambda a: (a.arrival, a.app_id))
    return {"instances": insts, "rate_per_s": rate, "mean_demand_s": e_s,
            "last_arrival": max(i.arrival for i in insts)}


def same_trace(a: Sequence, b: Sequence) -> bool:
    """True when two traces agree on every arrival time, app name, tenant,
    trajectory and deadline."""
    return len(a) == len(b) and all(
        x.arrival == y.arrival and x.app_name == y.app_name
        and x.tenant == y.tenant and x.trajectory == y.trajectory
        and x.deadline == y.deadline
        for x, y in zip(a, b))
