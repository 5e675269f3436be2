"""Fused RMSNorm kernel: one HBM read + one write per row tile, f32 reduction
in VMEM (the XLA fallback reads x twice — once for the mean-square, once for
the scale — unless fusion catches it)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                # (bm, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) *
                  scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_kernel(x: jnp.ndarray, scale: jnp.ndarray, *, eps: float = 1e-5,
                   block_rows: int = 256, interpret: bool = False) -> jnp.ndarray:
    """x: (rows, D); scale: (D,)."""
    rows, D = x.shape
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0
    kernel = functools.partial(_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, scale)
