"""Gittins-policy rank computation (§3.3).

    G(D, a) = inf_{Δ>0}  E[min(X−a, Δ) | X>a] / P(X−a ≤ Δ | X>a)

Lower rank = higher priority; for a deterministic X the rank equals the true
remaining time, so Gittins degrades gracefully to SRPT.  Two equivalent
implementations:

* ``gittins_rank_samples`` — numpy, exact over a raw sample list (test oracle).
* ``gittins_rank_hist``    — jitted, vectorized over the whole job queue on a
  bucketized (histogram) representation; this is the per-bucket-tick hot path
  whose runtime Fig. 15 reports.

When the attained service exceeds every recorded sample the distribution
carries no more information; we clamp `a` to just below the max sample (the
job then competes with rank ≈ the top-bucket width) — see DESIGN.md.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

N_BUCKETS = 10
_INF = 1e30


def to_histogram(samples: np.ndarray, n_buckets: int = N_BUCKETS
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """(probs (n,), right edges (n,)) over [min, max] of the samples.

    Delegates to the vectorized batch implementation so the per-app and
    whole-queue paths share one binning definition (bit-identical results
    even for samples landing exactly on a bin edge)."""
    s = np.asarray(samples, np.float64).reshape(1, -1)
    probs, edges = to_histogram_batch(s, n_buckets)
    return probs[0], edges[0]


def to_histogram_batch(samples: np.ndarray, n_buckets: int = N_BUCKETS
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Row-wise ``to_histogram`` without the per-app Python loop.

    samples: (A, W) — one row of raw demand samples per application.
    Returns (probs (A, n), right edges (A, n)).  Bins are uniform over
    [min, max], right-open with the last bin closed; this floor-based
    assignment is THE binning definition for both the per-app and batched
    paths (``to_histogram`` delegates here), so the two can never diverge
    on edge-coincident samples.
    """
    s = np.asarray(samples, np.float64)
    A, W = s.shape
    lo = s.min(axis=1)
    hi = s.max(axis=1)
    hi = np.where(hi <= lo, lo + np.maximum(np.abs(lo) * 1e-3, 1e-6), hi)
    norm = n_buckets / (hi - lo)
    idx = ((s - lo[:, None]) * norm[:, None]).astype(np.int64)
    np.clip(idx, 0, n_buckets - 1, out=idx)
    flat = idx + (np.arange(A) * n_buckets)[:, None]
    cnt = np.bincount(flat.ravel(), minlength=A * n_buckets) \
        .reshape(A, n_buckets)
    probs = cnt / max(W, 1)
    edges = np.linspace(lo, hi, n_buckets + 1, axis=1)[:, 1:]
    return probs.astype(np.float64), edges


def gittins_rank_samples(samples: np.ndarray, attained: float) -> float:
    """Exact empirical Gittins rank from raw samples (numpy oracle)."""
    s = np.sort(np.asarray(samples, np.float64))
    if len(s) and attained >= s[-1]:
        return float(attained)  # outlived the distribution: long-job prior
    a = float(attained) if len(s) else 0.0
    tail = s[s > a]
    if len(tail) == 0:
        tail = s[-1:]
    rem = tail - a                       # candidate Δ at each sample point
    n = len(rem)
    # for Δ = rem[j]: E[min(rem, Δ)] = (sum_{i<=j} rem_i + (n-j-1)*rem_j)/n
    csum = np.cumsum(rem)
    j = np.arange(n)
    e_min = (csum + (n - j - 1) * rem) / n
    p_le = (j + 1) / n
    return float(np.min(e_min / p_le))


def to_histogram_rows_jnp(total: jnp.ndarray, n_buckets: int = N_BUCKETS
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Device-side row-wise ``to_histogram_batch`` (float32, jit-safe).

    Same floor-based binning definition as the numpy batch path, evaluated
    in float32 on device so the fused refresh pipeline never ships the
    (A, n_walkers) sample matrix to the host.  Bucket counts come from a
    one-hot reduction (vectorizes where scatter-add would serialize on CPU).
    """
    W = total.shape[1]
    lo = total.min(axis=1)
    hi = total.max(axis=1)
    hi = jnp.where(hi <= lo, lo + jnp.maximum(jnp.abs(lo) * 1e-3, 1e-6), hi)
    norm = n_buckets / (hi - lo)
    idx = ((total - lo[:, None]) * norm[:, None]).astype(jnp.int32)
    idx = jnp.clip(idx, 0, n_buckets - 1)
    onehot = (idx[:, :, None] == jnp.arange(n_buckets)[None, None, :])
    # explicit reciprocal-multiply, NOT division by a constant: compiled
    # contexts (the Pallas kernel epilogue included) rewrite div-by-constant
    # to mul-by-reciprocal, so only the mul form has the same bits everywhere
    probs = onehot.sum(axis=1).astype(jnp.float32) * np.float32(
        1.0 / max(W, 1))
    frac = jnp.asarray(hist_fracs(n_buckets))
    # the max consumes the product so the following add cannot FMA-contract
    # it — contraction choices differ per compiled program and edge bits
    # must not depend on which program traced this twin.  Value-level
    # identity: span > 0 after the guard and frac > 0, so the product is
    # already non-negative (and the compiler cannot prove it).
    span_frac = jnp.maximum((hi - lo)[:, None] * frac[None, :], 0.0)
    edges = lo[:, None] + span_frac
    # pin the last edge to hi exactly (float32 lo + (hi-lo) can round off by
    # an ulp; np.linspace pins the endpoint, and `exhausted` compares to it)
    edges = edges.at[:, -1].set(hi)
    return probs, edges


def hist_fracs(n_buckets: int = N_BUCKETS) -> np.ndarray:
    """Right-edge fractions ``(b + 1) / n`` as float32 reciprocal-multiplies
    (div-by-constant is rewritten inconsistently across compilation
    contexts).  Host constants, so every program — the Pallas kernel takes
    them as scalar literals — multiplies the same folded values."""
    return np.arange(1, n_buckets + 1, dtype=np.float32) \
        * np.float32(1.0 / n_buckets)


def _sum_last(x: jnp.ndarray) -> jnp.ndarray:
    """Sum over the small static last axis, left to right, keeping it as
    size 1: one association order in every compiled program (XLA's and the
    TPU kernel compiler's reduction trees differ), so kernel and oracle
    sums agree to the bit."""
    acc = x[..., 0:1]
    for k in range(1, x.shape[-1]):
        acc = acc + x[..., k:k + 1]
    return acc


def _rank_prep(probs, edges, attained_col):
    """Per-bucket terms shared by both rank forms: bucket midpoints past
    the (clamped) attained service, their conditional mass and remaining
    service."""
    left = jnp.concatenate(
        [edges[:, :1] * 0 + (2 * edges[:, :1] - edges[:, 1:2]),
         edges[:, :-1]], axis=1)
    mids = 0.5 * (left + edges)                                  # (J, n)
    max_edge = edges[:, -1:]
    exhausted = attained_col >= max_edge                         # outlived dist
    a = jnp.minimum(attained_col, max_edge * (1 - 1e-6))         # (J, 1)
    alive = mids > a                                             # buckets past a
    p_tail = jnp.where(alive, probs, 0.0)
    tail_mass = jnp.maximum(_sum_last(p_tail), 1e-12)
    p_cond = p_tail / tail_mass                                  # (J, n)
    rem = jnp.where(alive, mids - a, 0.0)                        # (J, n)
    return exhausted, alive, p_cond, rem


def _candidate_terms(rem, delta, p_cond):
    """E[min(X - a, Δ)] and P(X - a <= Δ) summands for candidate ``delta``;
    the max consumes each product so no compiler can FMA-contract it into
    the sum."""
    return (jnp.maximum(jnp.minimum(rem, delta) * p_cond, 0.0),
            jnp.where(rem <= delta, p_cond, 0.0))


def _ratio(e_min, p_le, alive):
    return jnp.where((p_le > 1e-12) & alive,
                     e_min / jnp.maximum(p_le, 1e-12), _INF)


def gittins_rank_core(probs: jnp.ndarray, edges: jnp.ndarray,
                      attained: jnp.ndarray) -> jnp.ndarray:
    """Vectorized Gittins ranks for a whole queue (pure jnp; traced both by
    the standalone ``gittins_rank_hist`` jit and inline by the fused
    refresh pipeline).

    probs: (J, n_buckets) bucket probabilities per job
    edges: (J, n_buckets) right bucket edges (midpoints used as bucket values)
    attained: (J,) service received so far
    returns (J,) ranks.
    """
    exhausted, alive, p_cond, rem = _rank_prep(probs, edges,
                                               attained[:, None])
    # candidate Δ = rem at each alive bucket;  (J, n_delta, n_bucket)
    e_terms, p_terms = _candidate_terms(rem[:, None, :], rem[:, :, None],
                                        p_cond[:, None, :])
    ratio = _ratio(_sum_last(e_terms)[..., 0], _sum_last(p_terms)[..., 0],
                   alive)
    ranks = jnp.min(ratio, axis=1)
    # a job that outlived every recorded sample carries no hazard information;
    # the conservative completion (decreasing-hazard / heavy-tail prior) is to
    # treat it as a long job: rank grows with attained instead of collapsing
    # into the last bucket (which would hand runaway jobs top priority)
    return jnp.where(exhausted[:, 0], attained, ranks)


def rank_rows_loop(probs: jnp.ndarray, edges: jnp.ndarray,
                   attained_col: jnp.ndarray) -> jnp.ndarray:
    """:func:`gittins_rank_core` in 2-D-only form (kernel-traceable).

    Bit-identical twin that unrolls the candidate-Δ axis into a static
    loop: each candidate's numerator/denominator is the same float32
    left-to-right sum (:func:`_sum_last`) of the same terms, and the final
    ``min`` is order-independent, so the two can never diverge.  The Pallas
    fused-rank epilogue traces this over a ``(rows, n_buckets)`` tile;
    ``tests/test_fused_rank.py`` pins the twins bitwise.

    ``attained_col`` is ``(J, 1)`` (a column, not the core's ``(J,)`` —
    every intermediate stays 2-D); returns ``(J, 1)`` ranks."""
    exhausted, alive, p_cond, rem = _rank_prep(probs, edges, attained_col)
    ranks = None
    for j in range(edges.shape[1]):
        e_terms, p_terms = _candidate_terms(rem, rem[:, j:j + 1], p_cond)
        ratio = _ratio(_sum_last(e_terms), _sum_last(p_terms),
                       alive[:, j:j + 1])
        ranks = ratio if ranks is None else jnp.minimum(ranks, ratio)
    return jnp.where(exhausted, attained_col, ranks)


gittins_rank_hist = jax.jit(gittins_rank_core)


def gittins_rank_hist_np(probs: np.ndarray, edges: np.ndarray,
                         attained: np.ndarray) -> np.ndarray:
    """Numpy twin (used when jit warmup would dominate tiny queues).

    Pads the queue axis to a power of two before dispatch — same policy as
    ``GittinsPolicy.ranks`` and the fused refresh pipeline — so ad-hoc
    callers (tests, figure benchmarks) don't churn a fresh jit executable
    for every distinct queue length."""
    from repro.core.pdgraph import _pow2_ceil
    probs = np.asarray(probs, np.float32)
    edges = np.asarray(edges, np.float32)
    attained = np.asarray(attained, np.float32)
    J = probs.shape[0]
    Jp = _pow2_ceil(J)
    if Jp > J:
        probs = np.concatenate([probs, np.tile(probs[-1:], (Jp - J, 1))])
        edges = np.concatenate([edges, np.tile(edges[-1:], (Jp - J, 1))])
        attained = np.concatenate([attained, np.zeros(Jp - J, np.float32)])
    return np.asarray(gittins_rank_hist(jnp.asarray(probs),
                                        jnp.asarray(edges),
                                        jnp.asarray(attained)))[:J]


def srpt_mean_rank(samples: np.ndarray, attained: float) -> float:
    """Mean-remaining rank (the SRPT-on-the-mean baseline §3.3 argues against).

    Can go negative when a job outlives its expectation — exactly the paper's
    'ironically negative remaining time' failure mode."""
    return float(np.mean(samples) - attained)
