"""Property suite for the online conjugate posterior (core/posterior.py).

The contracts the tentpole rests on:

* **zero observations change nothing** — ``posterior_tables`` over all-zero
  rows returns the prior CDF bitwise and a demand scale of literal 1.0, and
  a scheduler with ``posterior=PosteriorConfig()`` but no observations ranks
  bit-identically to ``posterior=None``;
* **batch updates commute** — any permutation of one observation batch folds
  into bit-identical sufficient statistics (``PosteriorState.fold`` sorts
  into a canonical order before accumulating);
* **the posterior mean converges** — the Gamma posterior predictive demand
  obeys ``post_mean - empirical = tau * (prior_mean - empirical)/(tau + n)``
  exactly, so it contracts toward the empirical mean as observations accrue;
* **sampled branch tables stay distributions** — posterior transition CDF
  rows are monotone in [0, 1] and terminate at 1.

Runs under the no-network hypothesis stub in tests/_stubs (positional
``@given`` over seeds, no fixtures inside property tests).
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.apps.suite import T_IN, T_OUT, build_knowledge_base
from repro.core.posterior import (END, STAT_COLS, PosteriorConfig,
                                  PosteriorState, posterior_tables,
                                  row_width)
from repro.core.refresh_config import RefreshConfig
from repro.core.scheduler import HermesScheduler

_KB = None


def _kb():
    """Module-lazy KB (hypothesis-driven tests can't take fixtures)."""
    global _KB
    if _KB is None:
        _KB = build_knowledge_base(n_trials=40, seed=3)
    return _KB


def _random_prior(rng, P, U):
    """A valid (P, U, U+1) float32 transition CDF + (P, U) positive means."""
    p = rng.uniform(0.05, 1.0, (P, U, U + 1)).astype(np.float32)
    p /= p.sum(axis=-1, keepdims=True)
    cum = np.cumsum(p, axis=-1).astype(np.float32)
    cum[..., -1] = 1.0
    mean = rng.uniform(0.5, 20.0, (P, U)).astype(np.float32)
    return cum, mean


def _random_rows(rng, P, U, p_zero=0.4):
    """Posterior rows with a mix of observed and all-zero (P, U) units."""
    rows = np.zeros((P, U, row_width(U)), np.float32)
    observed = rng.uniform(size=(P, U)) > p_zero
    counts = rng.integers(0, 6, (P, U, U + 1)).astype(np.float32)
    rows[..., :U + 1] = counts * observed[..., None]
    dcnt = rng.integers(1, 9, (P, U)).astype(np.float32) * observed
    rows[..., U + 1] = dcnt * rng.uniform(0.1, 30.0, (P, U)).astype(
        np.float32)
    rows[..., U + 2] = dcnt
    return rows, observed


# ---------------------------------------------------------------- zero-obs

@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_zero_observation_tables_are_bitwise_prior(seed):
    rng = np.random.default_rng(seed)
    P, U = int(rng.integers(1, 12)), int(rng.integers(1, 6))
    cum, mean = _random_prior(rng, P, U)
    zero = np.zeros((P, U, row_width(U)), np.float32)
    po_cum, po_scale = posterior_tables(zero, cum, mean,
                                        branch_strength=8.0,
                                        demand_strength=8.0)
    np.testing.assert_array_equal(np.asarray(po_cum), cum)
    assert (np.asarray(po_scale) == np.float32(1.0)).all()


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_unobserved_units_keep_prior_rows_bitwise(seed):
    """Observed and unobserved units mix freely in one table: every
    unobserved (row, unit) stays bitwise prior even when neighbours moved."""
    rng = np.random.default_rng(seed)
    P, U = int(rng.integers(1, 10)), int(rng.integers(1, 5))
    cum, mean = _random_prior(rng, P, U)
    rows, observed = _random_rows(rng, P, U)
    po_cum, po_scale = posterior_tables(rows, cum, mean,
                                        branch_strength=4.0,
                                        demand_strength=4.0)
    po_cum, po_scale = np.asarray(po_cum), np.asarray(po_scale)
    branch_obs = rows[..., :U + 1].sum(axis=-1) > 0
    demand_obs = rows[..., U + 2] > 0
    np.testing.assert_array_equal(po_cum[~branch_obs], cum[~branch_obs])
    assert (po_scale[~demand_obs] == np.float32(1.0)).all()
    # observed demand units moved off the literal-1.0 path
    if demand_obs.any():
        assert np.isfinite(po_scale[demand_obs]).all()


@pytest.mark.parametrize("walker", ["pallas", "threefry"])
def test_scheduler_ranks_bitwise_identical_without_observations(walker):
    """posterior=PosteriorConfig() with an EMPTY observation stream ranks
    bit-identically to posterior=None across ticks and churn — the
    acceptance criterion's scheduler-level face."""
    kb = _kb()
    scheds = []
    for po in (None, PosteriorConfig()):
        s = HermesScheduler(kb, policy="gittins", t_in=T_IN, t_out=T_OUT,
                            mc_walkers=32, seed=11, posterior=po,
                            refresh=RefreshConfig(mode="fused_delta",
                                                  walker=walker))
        names = sorted(kb)
        for i in range(16):
            s.on_arrival(f"a{i:03d}", names[i % len(names)], now=0.25 * i)
            s.on_progress(f"a{i:03d}", 0.05 * i)
        scheds.append(s)
    a, b = scheds
    for t in (10.0, 11.0, 12.0):
        ra = a.refresh_tick(t, resample=True)
        rb = b.refresh_tick(t, resample=True)
        assert sorted(ra) == sorted(rb)
        for k in ra:
            assert ra[k] == rb[k], (walker, t, k)
        for s in (a, b):
            s.on_progress("a003", 1.0)
            s.on_app_complete(f"a{int(t) - 3:03d}")
            s.on_arrival(f"n{int(t)}", sorted(kb)[0], now=t)


def test_observations_move_only_the_observed_graph():
    """Demand observations re-rank re-walked slots of the OBSERVED graph;
    apps of other graphs keep their no-posterior ranks bitwise (their rows
    scatter as all-zero -> prior fallback)."""
    kb = _kb()

    def build(po):
        s = HermesScheduler(kb, policy="gittins", t_in=T_IN, t_out=T_OUT,
                            mc_walkers=32, seed=11, posterior=po,
                            refresh=RefreshConfig(mode="fused_delta"))
        names = sorted(kb)
        for i in range(8):
            s.on_arrival(f"a{i:03d}", names[i % len(names)], now=0.25 * i)
        return s

    a, b = build(None), build(PosteriorConfig())
    r0a = a.refresh_tick(10.0, resample=True)
    r0b = b.refresh_tick(10.0, resample=True)
    assert r0a == r0b
    target = b.apps["a000"]
    unit = kb[target.app_name].entry
    for s in (a, b):
        for _ in range(12):
            s.observe_unit_completion("a000", unit, 250.0)
        # posterior rows only refresh on a slot's walk: dirty both twins'
        # slots identically so the comparison isolates the observation feed
        s.on_requeue("a000", 10.5)
        s.on_requeue("a001", 10.5)
    r1a = a.refresh_tick(11.0, resample=True)
    r1b = b.refresh_tick(11.0, resample=True)
    assert r1b["a000"] != r1a["a000"]          # the observed graph moved
    same_graph = {i for i, app in b.apps.items()
                  if app.app_name == target.app_name}
    for k in r1a:
        if k not in same_graph:
            assert r1b[k] == r1a[k], k         # everyone else: bitwise prior


# ------------------------------------------------------------- commutativity

def _random_batch(rng, n):
    names = ("G0", "G1")
    units = ("u0", "u1", "u2")
    batch = []
    for _ in range(n):
        name = names[int(rng.integers(len(names)))]
        unit = units[int(rng.integers(len(units)))]
        if rng.uniform() < 0.5:
            nxt = (units + (END,))[int(rng.integers(len(units) + 1))]
            batch.append((name, unit, "branch", nxt))
        else:
            batch.append((name, unit, "demand",
                          float(np.float32(rng.uniform(0.01, 50.0)))))
    return batch


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_fold_commutes_under_permutation(seed):
    """Any permutation of one observation batch folds into bit-identical
    posterior rows (canonical in-batch sort order)."""
    rng = np.random.default_rng(seed)
    batch = _random_batch(rng, int(rng.integers(1, 40)))
    perm = list(rng.permutation(len(batch)))
    s1, s2 = PosteriorState(), PosteriorState()
    s1.fold(batch)
    s2.fold([batch[i] for i in perm])
    assert s1.n_observations() == s2.n_observations()
    for name in ("G0", "G1"):
        r1 = s1.graph_row(name, ["u0", "u1", "u2"], 3)
        r2 = s2.graph_row(name, ["u0", "u1", "u2"], 3)
        np.testing.assert_array_equal(r1, r2, err_msg=name)


def test_graph_row_layout():
    """Branch counts land at the packed next-unit index ($end at U), demand
    stats in the two trailing lanes; unknown units are dropped."""
    st_ = PosteriorState()
    st_.fold([("G", "u0", "branch", "u1"), ("G", "u0", "branch", "u1"),
              ("G", "u0", "branch", END), ("G", "u1", "demand", 2.5),
              ("G", "u1", "demand", 1.5), ("G", "gone", "demand", 9.9),
              ("G", "u1", "branch", "gone")])
    row = st_.graph_row("G", ["u0", "u1"], 2)
    assert row.shape == (2, row_width(2)) and row_width(2) == 2 + 1 + STAT_COLS
    assert row[0, 1] == 2.0                      # u0 -> u1 twice
    assert row[0, 2] == 1.0                      # u0 -> $end once
    assert row[1, 3] == np.float32(4.0)          # dsum u1
    assert row[1, 4] == 2.0                      # dcnt u1
    assert row[1, :3].sum() == 0.0               # u1 -> gone dropped
    assert (st_.graph_row("missing", ["u0", "u1"], 2) == 0.0).all()


# -------------------------------------------------------------- convergence

@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_posterior_demand_mean_contracts_to_empirical(seed):
    """post_mean - empirical == tau * (prior_mean - empirical) / (tau + n):
    the posterior predictive mean interpolates prior -> empirical with
    weight n/(tau+n), so it converges as observations accrue."""
    rng = np.random.default_rng(seed)
    tau = float(rng.choice([1.0, 4.0, 8.0, 32.0]))
    m = float(np.float32(rng.uniform(0.5, 20.0)))
    n = int(rng.integers(1, 400))
    obs = np.float32(rng.uniform(0.05, 40.0, n))
    S = np.float32(0.0)
    for o in obs:                     # float32 accumulation, as PosteriorState
        S = np.float32(S + o)
    rows = np.zeros((1, 1, row_width(1)), np.float32)
    rows[0, 0, 2] = S
    rows[0, 0, 3] = n
    cum = np.asarray([[[0.25, 1.0]]], np.float32)
    mean = np.asarray([[m]], np.float32)
    _, po_scale = posterior_tables(rows, cum, mean, branch_strength=8.0,
                                   demand_strength=tau)
    post_mean = float(np.asarray(po_scale)[0, 0]) * m
    emp = float(S) / n
    expect_gap = tau * (m - emp) / (tau + n)
    assert post_mean - emp == pytest.approx(expect_gap, rel=1e-4, abs=1e-4)
    # contraction: the residual prior pull shrinks ~1/n
    assert abs(post_mean - emp) <= tau * abs(m - emp) / (tau + n) + 1e-4


# ------------------------------------------------------------- normalization

@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_posterior_branch_tables_stay_distributions(seed):
    """Every posterior CDF row is monotone nondecreasing in [0, 1] and ends
    at 1 — the walk's inverse-CDF sampling stays a probability draw no
    matter what counts accumulated."""
    rng = np.random.default_rng(seed)
    P, U = int(rng.integers(1, 10)), int(rng.integers(1, 5))
    cum, mean = _random_prior(rng, P, U)
    rows, _ = _random_rows(rng, P, U, p_zero=0.2)
    po_cum, _ = posterior_tables(rows, cum, mean, branch_strength=2.0,
                                 demand_strength=2.0)
    po_cum = np.asarray(po_cum)
    assert (np.diff(po_cum, axis=-1) >= -1e-6).all()
    assert (po_cum >= 0.0).all() and (po_cum <= 1.0 + 1e-5).all()
    np.testing.assert_allclose(po_cum[..., -1], 1.0, atol=1e-5)


# ------------------------------------------------------------------- config

def test_posterior_config_validation():
    with pytest.raises(ValueError, match="branch_strength"):
        PosteriorConfig(branch_strength=0.0)
    with pytest.raises(ValueError, match="demand_strength"):
        PosteriorConfig(demand_strength=-1.0)
    assert PosteriorConfig().branch_strength == 8.0


def test_posterior_requires_fused_delta_mode():
    with pytest.raises(ValueError, match="fused_delta"):
        HermesScheduler(_kb(), policy="gittins",
                        refresh=RefreshConfig(mode="composed"),
                        posterior=PosteriorConfig())
