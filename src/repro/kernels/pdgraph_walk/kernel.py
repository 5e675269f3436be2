"""Pallas PDGraph random-walk kernel (counter-based in-kernel RNG).

One program instance advances a block of walker rows through ``n_steps``
transitions of the packed unit tables entirely in VMEM.  Design choices for
the TPU target:

* **walker rows** — flat walker state ``(N,)`` is laid out ``(R, L)``: one
  row of ``L`` lanes per app (``L = W``) on app-aligned walks, or any
  ``L | N`` chunk on compacted phases.  A grid step takes ``RB`` rows
  (a multiple of 8, or all of them — the TPU sublane tiling rule) and walks
  them one at a time, so the per-step ``(S, L)`` temporaries stay the size
  of one row whatever the block, and a row narrower than 128 lanes is legal;
* **one-hot matmuls instead of gathers** — TPU Pallas has no vectorized
  gather, so table rows are selected by ``table^T @ onehot(row)`` on the MXU
  (tables are passed pre-transposed: ``(S, G*U)`` / ``(U+1, G*U)``).  Each
  one-hot dot sums exactly one non-zero term and runs at ``HIGHEST``
  precision (a single bf16 pass would round the selected f32 value), which
  keeps the kernel bit-identical to the flat-gather jnp twin in ``ref.py``;
* **in-kernel counter RNG** — the per-step uniforms come from the shared
  ``fmix32`` hash over (stream, step*W + lane), so no threefry key chain is
  ever materialized and the RNG costs ~5 integer ops per walker-step;
* **app-blocked per-app tables** — refinement overrides and
  posterior-blended CDF/scale rows are per-APP ``(A*U, C)`` rows; a block
  walks ``RB`` whole apps and takes their ``RB*U`` rows, selected by a
  transposed-LHS one-hot dot ``(RB*U, C)^T @ (RB*U, W)``, so the VMEM
  footprint is independent of the queue length and no table is ever
  transposed in HBM;
* **fused-rank epilogue** — with ``with_rank`` / ``with_arr_hist`` the
  SAME program reduces each app's walker row to its demand-histogram row
  and per-unit arrival-histogram rows, then ranks the block's rows with
  :func:`repro.core.gittins.rank_rows_loop` (the one rank definition)
  before writing back: only ``(A, n_buckets)``-shaped products leave VMEM.

The interpret-mode path (auto off-TPU) runs the identical program through
the Pallas interpreter; the correctness sweeps in tests/test_pdgraph_walk.py
and tests/test_fused_rank.py check it bitwise against the twins and
distributionally (KS) against the threefry oracle `_walk_core`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.gittins import hist_fracs, rank_rows_loop
from repro.kernels.pdgraph_walk.ref import counter_uniforms

# scoped-VMEM budget: one (S, L) row walk keeps a handful of (1000, 512)
# float32 temporaries live (2 MB each) — over v5e's 16 MB default
_VMEM_LIMIT = 64 * 1024 * 1024
# row width of compacted (not app-aligned) phases: the largest divisor of
# the lane count up to this
_MAX_CHUNK = 512


def _select(table, onehot, rows_first=False):
    """Exact one-hot selection on the MXU (see module docstring):
    ``table_t (C, K) @ onehot (K, L)``, or with ``rows_first`` the
    untransposed ``table (K, C)`` contracted over its rows."""
    dims = ((0 if rows_first else 1,), (0,))
    return jax.lax.dot_general(table, onehot, (dims, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _put(acc, j, col):
    """``acc`` with column ``j`` replaced by the ``(rows, 1)`` ``col``."""
    return jnp.where(jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1) == j,
                     col, acc)


def _count(mask):
    return jnp.sum(jnp.where(mask, 1.0, 0.0), axis=1, keepdims=True)


def _kernel(*refs, step0: int, n_steps: int, lanes_per_app: int,
            with_overrides: bool, with_executed: bool, with_arrivals: bool,
            with_posterior: bool = False, n_buckets: int = 0,
            with_rank: bool = False, with_total_out: bool = True,
            arrival_never: float = 0.0):
    fused = n_buckets > 0
    it = iter(refs)
    samples_t_ref, counts_ref, cum_t_ref = (next(it) for _ in range(3))
    ovs_ref = next(it) if with_overrides else None
    ovc_ref = next(it) if with_overrides else None
    po_scale_ref = next(it) if with_posterior else None
    po_cum_ref = next(it) if with_posterior else None
    attained_ref = next(it) if fused else None
    cur_ref, gi_ref, stream_ref, lane_ref = (next(it) for _ in range(4))
    ex_ref = next(it) if with_executed else None
    if fused:
        total_ref = arr_ref = None
        done_ref = next(it)
        total_out_ref = next(it) if with_total_out else None
        if with_rank:
            probs_ref, edges_ref, ranks_ref = (next(it) for _ in range(3))
        arrstats_ref = next(it) if with_arrivals else None
    else:
        total_ref, done_ref = next(it), next(it)
        arr_ref = next(it) if with_arrivals else None
        cur_out_ref, total_out_ref, done_out_ref = \
            (next(it) for _ in range(3))
        arr_out_ref = next(it) if with_arrivals else None

    S, GU = samples_t_ref.shape
    U = cum_t_ref.shape[0] - 1               # absorbing state == unit stride
    RB, L = cur_ref.shape
    W = lanes_per_app
    nb = n_buckets
    per_app = with_overrides or with_posterior
    iota_gu = jax.lax.broadcasted_iota(jnp.int32, (GU, L), 0)
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (S, L), 0)
    if with_arrivals:
        iota_u = jax.lax.broadcasted_iota(jnp.int32, (U, L), 0)
    if per_app:
        iota_bau = jax.lax.broadcasted_iota(jnp.int32, (RB * U, L), 0)
    if with_overrides:
        So = ovs_ref.shape[1]
        iota_so = jax.lax.broadcasted_iota(jnp.int32, (So, L), 0)
    never = np.float32(arrival_never)

    def walk_row(r):
        """n_steps transitions of row ``r`` (one app when per-app tables
        or the fused epilogue are on)."""
        row_of = lambda ref: ref[pl.ds(r, 1), :]          # noqa: E731
        gi = row_of(gi_ref)
        stream = row_of(stream_ref)
        ex = row_of(ex_ref) if with_executed else None

        def step_fn(k, carry):
            cur, total, done, arr, ctr = carry  # (1,L) i32/f32/i32 (U,L) u32
            s = step0 + k
            r1, r2 = counter_uniforms(stream, ctr)
            roh = (iota_gu == gi * U + cur).astype(jnp.float32)   # (GU, L)
            n_eff = _select(counts_ref[...], roh)                 # (1, L)
            if per_app:
                aoh = (iota_bau == r * U + cur).astype(jnp.float32)
            if with_overrides:
                oc = _select(ovc_ref[...], aoh, True)             # (1, L)
                n_eff = jnp.where(oc > 0, oc, n_eff)
            si = jnp.floor(r1 * n_eff).astype(jnp.int32)          # (1, L)
            rowvals = _select(samples_t_ref[...], roh)            # (S, L)
            svc = jnp.sum(jnp.where(iota_s == si, rowvals, 0.0), axis=0,
                          keepdims=True)
            if with_overrides:
                ovals = _select(ovs_ref[...], aoh, True)          # (So, L)
                osel = iota_so == jnp.minimum(si, So - 1)
                osvc = jnp.sum(jnp.where(osel, ovals, 0.0), axis=0,
                               keepdims=True)
                svc = jnp.where(oc > 0, osvc, svc)
            if with_posterior:
                # max-guard mirrors walk_phase_ref: the max consumes the
                # product so downstream ops cannot FMA-contract it
                svc = jnp.maximum(
                    svc * _select(po_scale_ref[...], aoh, True), 0.0)
            if with_executed:
                svc = jnp.where(s == 0, jnp.maximum(svc - ex, 0.0), svc)
            alive = done == 0
            total = total + jnp.where(alive, svc, 0.0)
            cumsel = _select(po_cum_ref[...], aoh, True) if with_posterior \
                else _select(cum_t_ref[...], roh)                 # (U+1, L)
            # unrolled count of CDF entries below the draw (U+1 rows)
            nxt = (r2 > cumsel[0:1]).astype(jnp.int32)
            for j in range(1, U + 1):
                nxt = nxt + (r2 > cumsel[j:j + 1]).astype(jnp.int32)
            nxt = jnp.minimum(nxt, U)
            new_done = jnp.where(nxt >= U, 1, done)
            if with_arrivals:
                # entry into `nxt` happens when the current unit completes —
                # at the just-updated total; min keeps the first entry
                # (loops).  Same arithmetic as the twin's (N, U) onehot
                # update, laid out (U, L) so the select runs full-width.
                hit = (iota_u == nxt) & alive & (nxt < U)         # (U, L)
                arr = jnp.where(hit, jnp.minimum(arr, total), arr)
            cur = jnp.where(new_done != 0, cur, nxt)
            return cur, total, new_done, arr, ctr + np.uint32(W)

        # the RNG counter s*W + lane rides the carry as uint32 (a scalar
        # int->uint cast does not lower), wrapping exactly like the twin's
        ctr0 = row_of(lane_ref) + np.uint32((step0 * W) & 0xFFFFFFFF)
        if fused:
            total0 = jnp.zeros((1, L), jnp.float32)
            arr0 = jnp.full((U, L), never, jnp.float32)
        else:
            total0 = row_of(total_ref)
            arr0 = arr_ref[r] if with_arrivals \
                else jnp.zeros((1, L), jnp.float32)
        init = (row_of(cur_ref), total0, row_of(done_ref), arr0, ctr0)
        return jax.lax.fori_loop(0, n_steps, step_fn, init)[:4]

    def row_body(r, carry):
        cur, total, done, arr = walk_row(r)
        out_row = pl.ds(r, 1)
        if not fused:
            cur_out_ref[out_row, :] = cur
            total_out_ref[out_row, :] = total
            done_out_ref[out_row, :] = done
            if with_arrivals:
                arr_out_ref[r] = arr
            return carry
        if with_total_out:
            total_out_ref[out_row, :] = total
        if with_rank:
            # to_histogram_rows_jnp on `attained + max(rem, 0)`: the same
            # float ops, one app row at a time
            tot = attained_ref[out_row, :] + jnp.maximum(total, 0.0)
            lo = jnp.min(tot, axis=1, keepdims=True)              # (1, 1)
            hi = jnp.max(tot, axis=1, keepdims=True)
            hi = jnp.where(hi <= lo,
                           lo + jnp.maximum(jnp.abs(lo) * 1e-3, 1e-6), hi)
            idx = jnp.clip(((tot - lo) * (nb / (hi - lo))).astype(jnp.int32),
                           0, nb - 1)
            cnt = jnp.zeros((1, nb), jnp.float32)
            for b in range(nb):
                cnt = _put(cnt, b, _count(idx == b))
            edges = _put(jnp.zeros((1, nb), jnp.float32), nb - 1, hi)
            for b, frac in enumerate(hist_fracs(nb)[:-1]):
                edges = _put(edges, b,
                             lo + jnp.maximum((hi - lo) * frac, 0.0))
            probs_ref[out_row, :] = cnt * np.float32(1.0 / max(W, 1))
            edges_ref[out_row, :] = edges
        if with_arrivals:
            # mirrors refresh_pipeline._arrival_hists sum-for-sum; one row
            # per (app, unit): [hist | lo | span | n_reach]
            for u in range(U):
                arr_u = arr[u:u + 1]                              # (1, L)
                reached = arr_u < never / 2
                lo = jnp.min(jnp.where(reached, arr_u, never), axis=1,
                             keepdims=True)
                hi = jnp.max(jnp.where(reached, arr_u, -never), axis=1,
                             keepdims=True)
                span = jnp.maximum(hi - lo, 1e-6)
                idx = jnp.clip(((arr_u - lo) * (nb / span)).astype(jnp.int32),
                               0, nb - 1)
                st = jnp.zeros((1, nb + 3), jnp.float32)
                for b in range(nb):
                    st = _put(st, b, _count(reached & (idx == b)))
                st = _put(_put(_put(st, nb, lo), nb + 1, span), nb + 2,
                          _count(reached))
                arrstats_ref[pl.ds(r * U + u, 1), :] = st
        return carry

    jax.lax.fori_loop(0, RB, row_body, 0)
    if with_rank:
        ranks_ref[...] = rank_rows_loop(probs_ref[...], edges_ref[...],
                                        attained_ref[...])


def _rows_per_block(rows: int) -> int:
    """Rows a grid step walks: 8 (the sublane tile, the smallest legal
    block) or, when 8 does not divide ``rows``, all of them."""
    return 8 if rows % 8 == 0 else rows


def _call(kernel, operands, out_shape, rb, rows, interpret, name):
    """``operands``: ``(array, whole)`` — the whole array every step, or
    blocks of ``1 / nblk`` of its leading axis (``rb`` rows of walker
    state, ``rb * U`` rows of per-app tables).  ``name`` names the kernel's
    custom call in the compiled program, and so on the device trace."""
    nblk = rows // rb

    def spec(shape, whole):
        if whole:
            return pl.BlockSpec(shape, lambda i: (0,) * len(shape))
        blk = (shape[0] // nblk,) + tuple(shape[1:])
        return pl.BlockSpec(blk, lambda i: (i,) + (0,) * (len(shape) - 1))

    return list(pl.pallas_call(
        kernel,
        grid=(nblk,),
        in_specs=[spec(t.shape, whole) for t, whole in operands],
        out_specs=[spec(s.shape, False) for s in out_shape],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(*[t for t, _ in operands]))


def _operands(tables, ov_samples, ov_counts, po_scale, po_cum):
    """The shared tables whole, then the app-blocked per-app rows:
    overrides ``(A*U, So)`` / ``(A*U,)`` and posterior ``(A*U,)`` /
    ``(A*U, U+1)``."""
    samples_t, counts_row, cum_t = tables
    ops = [(samples_t, True), (counts_row.reshape(1, -1), True),
           (cum_t, True)]
    per_app = []
    if ov_samples is not None:
        per_app += [ov_samples, ov_counts[:, None]]
    if po_cum is not None:
        per_app += [po_scale[:, None], po_cum]
    return ops + [(t.astype(jnp.float32), False) for t in per_app]


def pdgraph_walk_kernel(samples_t, counts_row, cum_t,
                        cur, gi, stream, lane, executed, total, done,
                        arrivals=None, ov_samples=None, ov_counts=None,
                        po_scale=None, po_cum=None,
                        *, step0: int, n_steps: int, lanes_per_app: int,
                        interpret: bool = False):
    """Run one walk phase over flat walker state.

    State arrays are (N,); tables come pre-transposed (see module
    docstring).  ``executed`` (None = not applied) is consumed at global
    step 0.  ``arrivals`` (N, U) switches on the first-arrival carry: per
    walker, the cumulative service at its first entry into each unit rides
    the fori_loop as a (U, L) block and is returned as a fourth output.
    The per-app tables — overrides ``ov_samples (A*U, So)`` / ``ov_counts
    (A*U,)`` and posterior ``po_scale (A*U,)`` / ``po_cum (A*U, U+1)`` —
    are app-blocked, so with either the rows are apps (``L = W``) and the
    phase must cover app-major, uncompacted lanes.  Returns ``(cur, total,
    done)`` or ``(cur, total, done, arrivals)``.
    """
    N = cur.shape[0]
    W = lanes_per_app
    U = cum_t.shape[0] - 1
    per_app = ov_samples is not None or po_cum is not None
    L = W if per_app else math.gcd(N, _MAX_CHUNK)
    R = N // L
    rb = _rows_per_block(R)
    operands = _operands((samples_t, counts_row, cum_t),
                         ov_samples, ov_counts, po_scale, po_cum)
    state = [(cur, jnp.int32), (gi, jnp.int32), (stream, jnp.uint32),
             (lane, jnp.uint32)]
    if executed is not None:
        state.append((executed, jnp.float32))
    state += [(total, jnp.float32), (done, jnp.int32)]
    operands += [(a.astype(dt).reshape(R, L), False) for a, dt in state]
    out_shape = [jax.ShapeDtypeStruct((R, L), dt)
                 for dt in (jnp.int32, jnp.float32, jnp.int32)]
    if arrivals is not None:
        arr = arrivals.astype(jnp.float32).reshape(R, L, U) \
            .transpose(0, 2, 1)
        operands.append((arr, False))
        out_shape.append(jax.ShapeDtypeStruct(arr.shape, jnp.float32))
    kernel = functools.partial(
        _kernel, step0=step0, n_steps=n_steps, lanes_per_app=W,
        with_overrides=ov_samples is not None,
        with_executed=executed is not None,
        with_arrivals=arrivals is not None,
        with_posterior=po_cum is not None)
    out = _call(kernel, operands, out_shape, rb, R, interpret,
                "pdgraph_walk")
    res = (out[0].reshape(N), out[1].reshape(N), out[2].reshape(N) != 0)
    if arrivals is not None:
        res += (out[3].transpose(0, 2, 1).reshape(N, U),)
    return res


def pdgraph_walk_fused_kernel(samples_t, counts_row, cum_t, attained,
                              cur, gi, stream, lane, executed, done,
                              ov_samples=None, ov_counts=None,
                              po_scale=None, po_cum=None,
                              *, n_steps: int, lanes_per_app: int,
                              n_buckets: int, arrival_never: float,
                              with_arrivals: bool = False,
                              with_rank: bool = True,
                              with_total: bool = False,
                              interpret: bool = False):
    """The one-pass VMEM-resident refresh program: walk + per-app reduce.

    One ``pallas_call`` carries each app's walker row from transition
    sampling through the demand/arrival histogram rows and the Gittins
    rank — the ``(A, W)`` totals and ``(A, W, U)`` arrival tensor never
    leave VMEM unless ``with_total`` (triage) asks for the raw totals.
    Walks start at zero service (arrivals at ``arrival_never``) and are
    single-phase by construction (phase compaction is exact, so skipping
    it cannot change a bit — see ops.pdgraph_walk_ranked).  Per-app tables
    as in :func:`pdgraph_walk_kernel`.

    Returns ``(total (N,) | None, probs (A, nb) | None, edges | None,
    ranks (A,) | None, arrstats (A, U, nb+3) | None)`` — ``arrstats`` only
    ``with_arrivals``, packed ``[hist | lo | span | n_reach]`` per
    (app, unit).
    """
    N = cur.shape[0]
    W = lanes_per_app
    A = N // W
    U = cum_t.shape[0] - 1
    nb = n_buckets
    rb = _rows_per_block(A)
    operands = _operands((samples_t, counts_row, cum_t),
                         ov_samples, ov_counts, po_scale, po_cum)
    operands.append((attained.astype(jnp.float32).reshape(A, 1), False))
    operands += [(a.astype(dt).reshape(A, W), False) for a, dt in (
        (cur, jnp.int32), (gi, jnp.int32), (stream, jnp.uint32),
        (lane, jnp.uint32), (executed, jnp.float32), (done, jnp.int32))]
    out_shape = []
    if with_total:
        out_shape.append(jax.ShapeDtypeStruct((A, W), jnp.float32))
    if with_rank:
        out_shape += [jax.ShapeDtypeStruct((A, nb), jnp.float32)] * 2
        out_shape.append(jax.ShapeDtypeStruct((A, 1), jnp.float32))
    if with_arrivals:
        out_shape.append(jax.ShapeDtypeStruct((A * U, nb + 3), jnp.float32))
    kernel = functools.partial(
        _kernel, step0=0, n_steps=n_steps, lanes_per_app=W,
        with_overrides=ov_samples is not None, with_executed=True,
        with_arrivals=with_arrivals, with_posterior=po_cum is not None,
        n_buckets=nb, with_rank=with_rank, with_total_out=with_total,
        arrival_never=arrival_never)
    out = _call(kernel, operands, out_shape, rb, A, interpret,
                "pdgraph_walk_ranked")
    total_o = out.pop(0).reshape(N) if with_total else None
    probs_o = edges_o = ranks_o = None
    if with_rank:
        probs_o, edges_o = out.pop(0), out.pop(0)
        ranks_o = out.pop(0).reshape(A)
    arrstats_o = out.pop(0).reshape(A, U, nb + 3) if with_arrivals else None
    return total_o, probs_o, edges_o, ranks_o, arrstats_o
