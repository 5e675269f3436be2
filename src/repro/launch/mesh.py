"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod = 16x16 = 256 chips (TPU v5e pod slice);
multi-pod = 2 pods x 256 = 512 chips with a leading "pod" axis that maps to
DCN-connected data parallelism.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} "
            "(dryrun.py sets XLA_FLAGS=--xla_force_host_platform_device_count=512)")
    return jax.make_mesh(shape, axes, devices=devices[:n])


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    devices = jax.devices()
    dp = max(1, len(devices) // model_parallel)
    n = dp * model_parallel
    return Mesh(np.asarray(devices[:n]).reshape(dp, model_parallel),
                ("data", "model"))
