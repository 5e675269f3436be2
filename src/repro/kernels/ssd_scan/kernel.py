"""Mamba2 SSD chunk-scan kernel (state-space duality, TPU-adapted).

Grid (B, H, n_chunks); the chunk axis is 'arbitrary' (sequential) and the
running (N, P) SSM state lives in VMEM scratch across chunks.  Per chunk the
kernel does three MXU matmuls — C·Bᵀ (L×L intra-chunk panel), M·(x·dt)
(L×P), and C·state (L×P) — plus a rank-1 state update, so the chunk length L
(default 128) is the MXU tiling knob.  B/C projections are G=1 grouped and
shared across heads via the index_map (no HBM duplication).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_scr, *,
            chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, :, 0, :].astype(jnp.float32)        # (L, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)         # (L,)
    a = a_ref[0]                                     # scalar A (negative)
    bm = b_ref[0].astype(jnp.float32)                # (L, N)
    cm = c_ref[0].astype(jnp.float32)                # (L, N)

    dA = dt * a                                      # (L,)
    cum = jnp.cumsum(dA)                             # (L,)
    seg = cum[-1]

    # intra-chunk: M[l,s] = (C_l . B_s) * exp(cum_l - cum_s) * dt_s,  s <= l
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    rel = cum[:, None] - cum[None, :]
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(li >= si, jnp.exp(rel), 0.0)
    m = cb * decay * dt[None, :]
    y = jax.lax.dot(m, x, preferred_element_type=jnp.float32)     # (L, P)

    # inter-chunk: y += (C * exp(cum)) @ state
    state = state_scr[...]                                        # (N, P)
    y = y + jax.lax.dot(cm * jnp.exp(cum)[:, None], state,
                        preferred_element_type=jnp.float32)

    # state update: state = exp(seg)*state + sum_s exp(seg-cum_s)*dt_s B_s x_s
    w = jnp.exp(seg - cum) * dt                                   # (L,)
    upd = jax.lax.dot_general(bm * w[:, None], x, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (N, P)
    state_scr[...] = jnp.exp(seg) * state + upd
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)


def ssd_scan_kernel(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                    Bm: jnp.ndarray, Cm: jnp.ndarray, *, chunk: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """x: (B,S,H,P); dt: (B,S,H) (>0); A: (H,) (<0); Bm/Cm: (B,S,N).
    Returns y: (B,S,H,P)."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk

    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, S, H, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A, Bm, Cm)
