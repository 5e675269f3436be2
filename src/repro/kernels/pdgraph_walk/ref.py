"""Counter-RNG PDGraph walker: shared RNG primitives + pure-jnp twin.

The walker replaces the per-step threefry `jax.random.uniform` of
``repro.core.pdgraph._walk_core`` — the measured refresh-tick ceiling on CPU
— with a counter-based hash RNG (murmur3 finalizer over a per-walker Weyl
counter): every (walker, step) draws its 32 random bits from one 5-op integer
hash instead of a 20-round threefry block, and the same bits are computed
identically inside the Pallas kernel, in this jnp twin, and on any backend.

Two oracles back the kernel:

* ``walk_phase_ref`` (here) — the jnp twin: flat gathers instead of the
  kernel's one-hot matmuls, otherwise the same arithmetic, so kernel and twin
  are *bit-identical* (each one-hot dot sums exactly one non-zero term).
  Off-TPU this twin IS the fast dispatch path.
* ``repro.core.pdgraph._walk_core`` — the threefry oracle: the counter
  walker must match it in *distribution* (KS test), not bitwise.

16/16 bit split: one hash yields both per-step uniforms (demand-sample index
from the high 16 bits, transition draw from the low 16).  With <= 1000
demand samples per unit the floor allocation keeps the per-outcome CDF error
below 2**-16 — three orders of magnitude under what a KS test at n=10^4 can
resolve.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# numpy scalars (not jnp arrays): they trace to jaxpr literals, which Pallas
# kernels may close over — device-array constants they may not
_M1 = np.uint32(0x85EBCA6B)        # murmur3 fmix32 constants
_M2 = np.uint32(0xC2B2AE35)
GOLDEN = np.uint32(0x9E3779B9)     # Weyl increment (2**32 / phi)
_U16_SCALE = np.float32(1.0 / 65536.0)


def fmix32(x: jnp.ndarray) -> jnp.ndarray:
    """Murmur3 finalizer: full avalanche over uint32."""
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 13)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def counter_uniforms(stream: jnp.ndarray, ctr: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Two [0,1) float32 uniforms (16-bit resolution) from one hash of a
    per-walker stream id and a per-step counter."""
    bits = fmix32(stream + ctr * GOLDEN)
    # via int32 (same values, both < 2**16): the TPU kernel compiler has
    # no uint32 -> float32 conversion
    r = (bits >> 16).astype(jnp.int32).astype(jnp.float32) * _U16_SCALE
    r2 = (bits & np.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32) \
        * _U16_SCALE
    return r, r2


def walker_streams(seed, key_ids: jnp.ndarray, refresh_ids: jnp.ndarray
                   ) -> jnp.ndarray:
    """Per-(app, refresh) stream ids — the counter-RNG analogue of the
    scheduler's ``fold_in(fold_in(base_key, key_id), refreshes)`` chain."""
    s = fmix32(jnp.asarray(seed).astype(jnp.uint32)
               ^ (jnp.asarray(key_ids).astype(jnp.uint32) * GOLDEN))
    return fmix32(s ^ (jnp.asarray(refresh_ids).astype(jnp.uint32) * _M1))


def walk_phase_ref(fsamples: jnp.ndarray,     # (G*U, S) float32
                   fcounts: jnp.ndarray,      # (G*U,)  float32
                   fcum: jnp.ndarray,         # (G*U, U+1) float32
                   fov_samples: Optional[jnp.ndarray],  # (A*U, So) float32
                   fov_counts: Optional[jnp.ndarray],   # (A*U,)  float32
                   cur: jnp.ndarray, total: jnp.ndarray, done: jnp.ndarray,
                   gi: jnp.ndarray, app: jnp.ndarray,
                   stream: jnp.ndarray, lane: jnp.ndarray,
                   executed: Optional[jnp.ndarray],
                   *, step0: int, n_steps: int, lanes_per_app: int,
                   unroll: int = 4,
                   arrivals: Optional[jnp.ndarray] = None,
                   fpo_cum: Optional[jnp.ndarray] = None,   # (A*U, U+1)
                   fpo_scale: Optional[jnp.ndarray] = None):  # (A*U,)
    """One phase of the counter walk over flat walker state (N,).

    Tables are flattened row-major over (graph, unit) so one 1-D gather per
    lookup serves the whole mixed-graph queue; ``executed`` is only consumed
    at global step 0 (phase-2 calls pass None).  Returns updated
    ``(cur, total, done)``.

    ``arrivals`` (N, U) enables first-arrival tracking: each walker records
    its cumulative service at its FIRST entry into each unit
    (``ARRIVAL_NEVER`` where never entered) — the prewarm planner's input.
    The counter-RNG draws are indexed by (stream, lane, step) and do not
    depend on the extra carry, so totals are bit-identical either way.
    Returns ``(cur, total, done, arrivals)`` when tracking.

    ``fpo_cum`` / ``fpo_scale`` (flattened per-APP posterior walk tables,
    ``repro.core.posterior``) switch on posterior sampling: transitions draw
    against the app's posterior-blended CDF and sampled service is rescaled
    by the unit's posterior demand ratio.  Like the arrival carry, the RNG
    draws don't depend on them — ``None`` keeps the frozen-prior bits.
    """
    U = fcum.shape[1] - 1                    # absorbing state == unit stride
    S = fsamples.shape[1]
    fsv = fsamples.reshape(-1)
    with_ov = fov_samples is not None
    if with_ov:
        So = fov_samples.shape[1]
        fov = fov_samples.reshape(-1)
    with_po = fpo_cum is not None
    track = arrivals is not None
    unit_ids = jnp.arange(U, dtype=jnp.int32)

    def step(carry, s):
        cur, total, done, arr = carry
        ctr = s.astype(jnp.uint32) * np.uint32(lanes_per_app) + lane
        r, r2 = counter_uniforms(stream, ctr)
        row = gi * U + cur
        orow = app * U + cur if (with_ov or with_po) else None
        n_eff = fcounts[row]
        if with_ov:
            oc = fov_counts[orow]
            n_eff = jnp.where(oc > 0, oc, n_eff)
        si = jnp.floor(r * n_eff).astype(jnp.int32)
        svc = fsv[row * S + si]
        if with_ov:
            svc = jnp.where(oc > 0,
                            fov[orow * So + jnp.minimum(si, So - 1)], svc)
        if with_po:
            # the max consumes the product so no downstream add/sub can
            # FMA-contract it (contraction choices differ per compiled
            # program and would break kernel/twin bit-identity).  Value-
            # level identity: service samples and posterior scales are
            # non-negative, and the compiler cannot prove it.
            svc = jnp.maximum(svc * fpo_scale[orow], 0.0)
        if executed is not None:
            svc = jnp.where(s == 0, jnp.maximum(svc - executed, 0.0), svc)
        total = total + jnp.where(done, 0.0, svc)
        cdf = fpo_cum[orow] if with_po else fcum[row]
        nxt = jnp.sum(r2[:, None] > cdf, axis=-1).astype(jnp.int32)
        nxt = jnp.minimum(nxt, U)
        new_done = done | (nxt >= U)
        if track:
            # entry into `nxt` happens when the current unit completes — at
            # the just-updated total; min keeps the first entry (loops)
            enter = (~done) & (nxt < U)
            onehot = enter[:, None] & (nxt[:, None] == unit_ids[None, :])
            arr = jnp.where(onehot, jnp.minimum(arr, total[:, None]), arr)
        cur = jnp.where(new_done, cur, nxt)
        return (cur, total, new_done, arr), None

    arr0 = arrivals if track else jnp.zeros((cur.shape[0], 0), jnp.float32)
    steps = jnp.arange(step0, step0 + n_steps, dtype=jnp.int32)
    (cur, total, done, arr), _ = jax.lax.scan(
        step, (cur, total, done, arr0), steps,
        unroll=min(unroll, n_steps))
    return (cur, total, done, arr) if track else (cur, total, done)
