"""Grouped expert matmul (megablox-style) for the EP-MoE local compute.

Computes out[e] = x[e] @ w[e] for E experts over capacity-packed token
buffers — the kernel behind the `ep` MoE path's three einsums.  Grid
(E, C/bc, N/bn, D/bd): the D (contraction) axis is innermost/'arbitrary' and
accumulates in an f32 VMEM scratch tile; expert weights stream through VMEM
one (bd, bn) tile at a time, so VMEM holds bc*bd + bd*bn + bc*bn floats —
tile defaults (128, 512, 512) keep that ~1.3 MB.

Zero-padded capacity rows multiply through harmlessly (their outputs are
masked by the combine step), exactly like the XLA einsum they replace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, w_ref, o_ref, acc_scr, *, n_d: int):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0]                       # (bc, bd)
    w = w_ref[0]                       # (bd, bn)
    acc_scr[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(di == n_d - 1)
    def _done():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def moe_gmm_kernel(x: jnp.ndarray, w: jnp.ndarray, *, block_c: int = 128,
                   block_n: int = 512, block_d: int = 512,
                   interpret: bool = False) -> jnp.ndarray:
    """x: (E, C, D) capacity-packed tokens; w: (E, D, N). Returns (E, C, N)."""
    E, C, D = x.shape
    _, _, N = w.shape
    block_c = min(block_c, C)
    block_n = min(block_n, N)
    block_d = min(block_d, D)
    assert C % block_c == 0 and N % block_n == 0 and D % block_d == 0
    n_d = D // block_d

    kernel = functools.partial(_kernel, n_d=n_d)
    return pl.pallas_call(
        kernel,
        grid=(E, C // block_c, N // block_n, n_d),
        in_specs=[
            pl.BlockSpec((1, block_c, block_d), lambda e, c, n, d: (e, c, d)),
            pl.BlockSpec((1, block_d, block_n), lambda e, c, n, d: (e, d, n)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_n),
                               lambda e, c, n, d: (e, c, n)),
        out_shape=jax.ShapeDtypeStruct((E, C, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
