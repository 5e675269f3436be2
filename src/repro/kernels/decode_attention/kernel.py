"""Decode (single-token) attention kernel — flash-decoding style split-K.

Grid (B*K, n_s_blocks): the sequence axis is 'arbitrary' (sequential) and the
partial softmax state (m, l, acc) is carried in VMEM scratch, exactly the
combine the distributed seq-sharded decode path performs at the collective
level.  The per-batch valid length arrives via scalar prefetch (SMEM) so
beyond-`pos` cache slots are masked without touching HBM.

One tile = (block_s, hd) K/V + the (G, block_s) score panel — tiny; the kernel
is HBM-bandwidth-bound by design (that is what decode is).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_s: int, n_s: int):
    b = pl.program_id(0)
    si = pl.program_id(1)
    length = len_ref[b]

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                    # (G, hd)
    k = k_ref[0].astype(jnp.float32)                    # (bs, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (G,bs)
    spos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = spos < length
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)                    # (bs, hd)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(si == n_s - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def decode_attention_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            lengths: jnp.ndarray, *, block_s: int = 512,
                            interpret: bool = False) -> jnp.ndarray:
    """q: (BK, G, hd); k/v: (BK, Smax, hd); lengths: (BK,) int32 valid length.
    Returns (BK, G, hd)."""
    BK, G, hd = q.shape
    _, Smax, _ = k.shape
    block_s = min(block_s, Smax)
    assert Smax % block_s == 0
    n_s = Smax // block_s
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(_kernel, scale=scale, block_s=block_s, n_s=n_s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BK, n_s),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda b, si, lens: (b, 0, 0)),
            pl.BlockSpec((1, block_s, hd), lambda b, si, lens: (b, si, 0)),
            pl.BlockSpec((1, block_s, hd), lambda b, si, lens: (b, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda b, si, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BK, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, k, v)
