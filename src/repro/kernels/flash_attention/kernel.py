"""Flash-attention prefill kernel (TPU, MXU-tiled).

Grid (B*K*G, n_q_blocks, n_kv_blocks); the kv-block axis is 'arbitrary'
(sequential) so the online-softmax state (m, l, acc) lives in VMEM scratch
across kv steps.  GQA is folded into the index_map: query row b covers
(batch, kv_head, group) = (b // (K*G), (b // G) % K, b % G) and the K/V specs
map b -> b // G, so grouped queries share one KV tile without materializing
repeated KV in HBM.

Block sizes default to (128, 512): q tile (128, hd) + kv tiles (512, hd) +
(128, 512) f32 scores stay well under the ~128 KiB/lane VMEM budget for
hd <= 256, and 128 rows align with the MXU systolic dimension.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, block_q: int, block_kv: int,
            n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)              # (bq, hd)
    k = k_ref[0].astype(jnp.float32)              # (bkv, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

    m_prev = m_scr[...]                           # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                        # (bq, bkv)
    alpha = jnp.exp(m_prev - m_new)               # (bq, 1)
    l_new = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)              # (bkv, hd)
    acc = acc_scr[...] * alpha + jax.lax.dot(p, v,
                                             preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, block_q: int = 128,
                           block_kv: int = 512,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (BKG, Sq, hd) with rows ordered (batch, kv_head, group);
    k/v: (BK, Skv, hd).  Returns (BKG, Sq, hd)."""
    BKG, Sq, hd = q.shape
    BK, Skv, _ = k.shape
    G = BKG // BK
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0
    n_q, n_kv = Sq // block_q, Skv // block_kv
    scale = 1.0 / math.sqrt(hd)

    grid = (BKG, n_q, n_kv)
    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_kv=block_kv, n_kv=n_kv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, qi, ki: (b // G, ki, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, qi, ki: (b // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BKG, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
