"""Expert-parallel MoE via shard_map + all_to_all (the hillclimbed MoE path).

The GSPMD `sort` baseline routes through gathers/scatters on globally-sharded
buffers, which XLA lowers to per-layer all-gathers of the full (T, D) token
tensor — the dominant collective in the MoE baseline cells (EXPERIMENTS
§Perf).  This implementation makes the communication explicit and minimal:

  1. the local (data-shard) token block is split across the `model` axis —
     each model-rank routes Tc = T_local/n tokens;
  2. tokens are packed into per-destination capacity buffers and exchanged
     with ONE all_to_all over `model` (bytes ≈ k·cf·Tc·D, not T·D);
  3. each rank runs its E/n experts on what it received (second, local,
     capacity packing per expert);
  4. one reverse all_to_all returns expert outputs; weights are applied at
     the origin (gate weights never cross the wire);
  5. a final all-gather over `model` restores the replicated activation
     layout the surrounding TP layers expect.

Wire bytes per layer ≈ 2·(k·cf·Tc·D) + Tl·D  versus the baseline's
2·(Tl·D)·(fwd+bwd all-gathers) — measured in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.distributed.sharding import current_ctx
from repro.models.layers import padded_experts

Params = Dict[str, jnp.ndarray]


def _axis_prod(mesh, axes) -> int:
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def _pack_by_key(keys: jnp.ndarray, n_bins: int, capacity: int):
    """Sort-free capacity packing: returns (order, bin_ids, pos, keep) such
    that scattering item order[i] into (bin_ids[i], pos[i]) packs each bin
    densely, dropping overflow (keep)."""
    order = jnp.argsort(keys)
    sorted_keys = keys[order]
    counts = jnp.bincount(keys, length=n_bins)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(keys.shape[0]) - starts[sorted_keys]
    keep = pos < capacity
    return order, sorted_keys, jnp.where(keep, pos, 0), keep


def moe_apply_ep(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """x: (B, S, D) with batch sharded over the data axes and replicated over
    `model`; expert weights sharded over `model` on the expert dim."""
    ctx = current_ctx()
    if ctx is None or ctx.model_axis is None:
        from repro.models.moe import moe_apply_sort
        return moe_apply_sort(p, x, cfg)
    mesh = ctx.mesh
    model_ax = ctx.model_axis
    n = mesh.shape[model_ax]
    batch_axes = ctx.batch_axes

    E = padded_experts(cfg.num_experts)
    B, S, D = x.shape
    if E % n or (B * S) % (n * max(_axis_prod(mesh, batch_axes), 1)):
        from repro.models.moe import moe_apply_sort
        return moe_apply_sort(p, x, cfg)   # tiny/ragged cases
    E_local = E // n
    k = cfg.top_k

    in_spec = P(batch_axes if batch_axes else None, None, None)
    w_expert = P(model_ax, None, None)
    router_spec = P(*([None] * p["router"].ndim))

    def body(xl, router, wi, wg, wo):
        B_l, S, D = xl.shape
        Tl = B_l * S
        r = jax.lax.axis_index(model_ax)
        Tc = max(Tl // n, 1)
        xf = xl.reshape(Tl, D)
        xc = jax.lax.dynamic_slice_in_dim(xf, r * Tc, Tc, axis=0)

        logits = (xc.astype(jnp.float32) @ router)               # (Tc, E_real)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        flat_e = top_i.reshape(-1)                               # (Tc*k,)
        flat_t = jnp.repeat(jnp.arange(Tc), k)
        flat_w = top_p.reshape(-1)
        dest = flat_e // E_local                                 # target rank

        C = max(8, int(math.ceil(Tc * k * cfg.capacity_factor / n / 8)) * 8)
        order, dest_s, pos, keep = _pack_by_key(dest, n, C)
        t_s, e_s, w_s = flat_t[order], flat_e[order], flat_w[order]

        send = jnp.zeros((n, C, D), xl.dtype)
        send = send.at[dest_s, pos].add(
            jnp.where(keep[:, None], xc[t_s], 0).astype(xl.dtype))
        send_eid = jnp.full((n, C), -1, jnp.int32)
        send_eid = send_eid.at[dest_s, pos].set(
            jnp.where(keep, e_s % E_local, -1))

        recv = jax.lax.all_to_all(send, model_ax, 0, 0, tiled=True)
        recv_eid = jax.lax.all_to_all(send_eid, model_ax, 0, 0, tiled=True)
        rtok = recv.reshape(n * C, D)
        reid = recv_eid.reshape(n * C)

        # local per-expert packing (padding expert E_local for invalid slots)
        eid_for_pack = jnp.where(reid >= 0, reid, E_local)
        C2 = max(8, int(math.ceil(n * C * 1.3 / E_local / 8)) * 8)
        o2, e2, pos2, keep2 = _pack_by_key(eid_for_pack, E_local + 1, C2)
        valid2 = keep2 & (e2 < E_local)
        buf = jnp.zeros((E_local, C2, D), xl.dtype)
        buf = buf.at[jnp.where(valid2, e2, 0), pos2].add(
            jnp.where(valid2[:, None], rtok[o2], 0))

        h = jnp.einsum("ecd,edf->ecf", buf, wi)
        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        out_e = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo)

        back = jnp.zeros((n * C, D), xl.dtype)
        back = back.at[o2].add(
            jnp.where(valid2[:, None],
                      out_e[jnp.where(valid2, e2, 0), pos2], 0))
        back = jax.lax.all_to_all(back.reshape(n, C, D), model_ax, 0, 0,
                                  tiled=True)

        yc = jnp.zeros((Tc, D), jnp.float32)
        contrib = back[dest_s, pos] * (w_s * keep)[:, None].astype(xl.dtype)
        yc = yc.at[t_s].add(contrib.astype(jnp.float32))

        y = jax.lax.all_gather(yc.astype(xl.dtype), model_ax, axis=0,
                               tiled=True)                        # (Tl, D)
        return y.reshape(B_l, S, D)

    inner = jax.shard_map(body, mesh=mesh,
                          in_specs=(in_spec, router_spec, w_expert, w_expert,
                                    w_expert),
                          out_specs=in_spec, check_vma=False)
    y = inner(x, p["router"].astype(jnp.float32), p["wi"], p["wg"], p["wo"])

    if cfg.num_shared_experts:
        from repro.models.moe import _shared_expert
        y = y + _shared_expert(p, x, cfg)
    return y
