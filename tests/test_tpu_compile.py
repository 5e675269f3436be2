"""The main-path kernels compile for a described TPU v5e at real widths.

Nothing runs: each case lowers and compiles a kernel program for one chip of
a described ``v5e:2x2`` topology (the TPU compiler is installed, the chip is
not), so what the chip's compiler refuses — tile shapes, casts, loop
carries, scoped VMEM — fails here instead of on the chip.  Widths are the
simulator's: 1,000 demand samples per unit (the benchmark knowledge base),
1,000-sample refinement overrides, a 16,384-app dirty set, W = 256 (the
simulator's walkers) and 512 (the scheduler's), and a capped W below 128.

The topology is described in a module-scoped fixture, never at import:
only the test worker that runs this file may load the TPU library.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.pdgraph_walk.ops import pdgraph_walk, pdgraph_walk_ranked

G, U, S, SO, NB = 10, 4, 1000, 1000, 10
A = 16384


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any backend error means "absent"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _args(sharding, n_apps, *, overrides, posterior):
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=sharding)
    rows = dict(samples=sd((G, U, S)), counts=sd((G, U)),
                cum_trans=sd((G, U, U + 1)),
                graph_idx=sd((n_apps,), jnp.int32),
                start=sd((n_apps,), jnp.int32), executed=sd((n_apps,)),
                streams=sd((n_apps,), jnp.uint32), attained=sd((n_apps,)))
    if overrides:
        rows.update(ov_samples=sd((n_apps, U, SO)),
                    ov_counts=sd((n_apps, U), jnp.int32))
    if posterior:
        rows.update(po_cum=sd((n_apps, U, U + 1)), po_scale=sd((n_apps, U)))
    return rows


def _compile(fn, rows, kernel):
    compiled = jax.jit(fn).lower(rows).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's name is its custom call's, and so the device trace's
    assert re.search(rf"%{kernel}(\.\d+)? = ", text)
    return compiled


@pytest.mark.parametrize("walkers,overrides,arrivals,posterior", [
    (256, False, True, True),
    (512, False, True, True),
    (256, True, True, False),
    (32, True, False, False),
])
def test_ranked_kernel_compiles(one_chip, walkers, overrides, arrivals,
                                posterior):
    """The one-pass refresh program (walk + histograms + rank)."""
    rows = _args(one_chip, A, overrides=overrides, posterior=posterior)

    def fn(r):
        out = pdgraph_walk_ranked(
            r["samples"], r["counts"], r["cum_trans"], r["graph_idx"],
            r["start"], r["executed"], r["streams"], r["attained"],
            r.get("ov_samples"), r.get("ov_counts"), n_walkers=walkers,
            n_buckets=NB, impl="pallas", interpret=False,
            track_arrivals=arrivals, po_cum=r.get("po_cum"),
            po_scale=r.get("po_scale"))
        return out["ranks"], out["probs"], out["edges"], out.get("a_hist")

    _compile(fn, rows, "pdgraph_walk_ranked")


@pytest.mark.parametrize("walkers,overrides,n_apps", [
    (512, True, A),       # per-app tables: one app-aligned phase
    (256, False, 1024),   # compacted phases
])
def test_walk_phases_compile(one_chip, walkers, overrides, n_apps):
    """The plain walk phases with the first-arrival carry."""
    rows = _args(one_chip, n_apps, overrides=overrides, posterior=False)

    def fn(r):
        total, arr, spill = pdgraph_walk(
            r["samples"], r["counts"], r["cum_trans"], r["graph_idx"],
            r["start"], r["executed"], r["streams"], r.get("ov_samples"),
            r.get("ov_counts"), n_walkers=walkers, impl="pallas",
            interpret=False, track_arrivals=True)
        return total, arr, spill

    _compile(fn, rows, "pdgraph_walk")
