"""Pallas TPU kernels for the compute hot-spots.

Each kernel package ships:
  kernel.py  pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py     jit'd public wrapper (auto interpret=True off-TPU)
  ref.py     pure-jnp oracle used by the allclose test sweeps
"""
