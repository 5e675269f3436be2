"""The plain reference against the program's refresh on the CPU (the jnp
twin, bit-identical to the kernel), and its control: the reference at the
next precision down (a three-pass bfloat16 selection) must fail the
limits the check holds the program to."""
import numpy as np
import pytest

import jax.numpy as jnp

from bench import check, reference
from bench.harness import load_limits
from repro.apps.suite import T_IN, T_OUT, build_knowledge_base
from repro.core.gittins import gittins_rank_core, to_histogram_rows_jnp
from repro.core.pdgraph import pack_graphs
from repro.kernels.pdgraph_walk.ops import pdgraph_walk, walker_streams

W, NB, SEED = 256, 10, 1234567


@pytest.fixture(scope="module")
def kb():
    return build_knowledge_base(n_trials=200, seed=3)


def _apps(kb, n=24, seed=0):
    """Application records as the harness captures them; every third
    carries a refinement override."""
    rng = np.random.default_rng(seed)
    names = sorted(kb)
    apps = []
    for i in range(n):
        name = names[int(rng.integers(len(names)))]
        units = sorted(kb[name].units)
        ov = {}
        if i % 3 == 0:
            k = int(rng.integers(5, 64))
            ov[units[int(rng.integers(len(units)))]] = \
                rng.gamma(2.0, 3.0, k).astype(np.float32)
        apps.append({"app": name,
                     "unit": units[int(rng.integers(len(units)))],
                     "executed": np.float32(rng.uniform(0, 2)),
                     "attained": np.float32(rng.uniform(0, 30)),
                     "key_id": int(rng.integers(0, 10 ** 6)),
                     "refresh_id": int(rng.integers(0, 50)),
                     "overrides": ov})
    return apps


def _rows(kb, n=24, seed=0):
    tables = reference.build_tables(kb, T_IN, T_OUT)
    apps = _apps(kb, n, seed)
    rows = reference.rows_from_apps(tables, apps)
    rows["ov_samples"], rows["ov_counts"] = reference._override_rows(
        tables, rows["graph"], rows["overrides"])
    return tables, apps, rows


def _program(kb, rows):
    p = pack_graphs(kb, T_IN, T_OUT)
    st = walker_streams(np.uint32(SEED), jnp.asarray(rows["key_id"]),
                        jnp.asarray(rows["refresh_id"]))
    rem, _ = pdgraph_walk(p.samples, p.counts, p.cum_trans,
                          jnp.asarray(rows["graph"]),
                          jnp.asarray(rows["start"]),
                          jnp.asarray(rows["executed"]), st,
                          jnp.asarray(rows["ov_samples"]),
                          jnp.asarray(rows["ov_counts"]), n_walkers=W,
                          impl="ref", compact_schedule=())
    att = jnp.asarray(rows["attained"])
    probs, edges = to_histogram_rows_jnp(att[:, None]
                                         + jnp.maximum(rem, 0.0), NB)
    return (np.asarray(probs), np.asarray(edges),
            np.asarray(gittins_rank_core(probs, edges, att)))


def test_tables_follow_the_knowledge_base(kb):
    tables = reference.build_tables(kb, T_IN, T_OUT)
    p = pack_graphs(kb, T_IN, T_OUT)
    assert tables["graph"] == p.graph_index
    assert np.array_equal(tables["samples"], np.asarray(p.samples))
    assert np.array_equal(tables["cdf"], np.asarray(p.cum_trans))
    assert np.array_equal(tables["counts"], np.asarray(p.counts, np.float32))


def test_reference_matches_the_program_bit_for_bit(kb):
    tables, apps, rows = _rows(kb)
    assert rows["ov_counts"].any()
    probs, edges, ranks = _program(kb, rows)
    rp, re_ = reference.refresh_rows(tables, rows, seed=SEED, n_walkers=W,
                                     n_buckets=NB, block=7)
    assert np.array_equal(rp, probs)
    assert np.array_equal(re_, edges)
    assert np.array_equal(reference.gittins(rp, re_, rows["attained"]),
                          ranks)


def test_control_fails_the_limits(kb):
    tables, apps, rows = _rows(kb, seed=1)
    n = len(rows["graph"])
    occ = np.arange(n)
    probs, edges, ranks = _program(kb, rows)
    cap = check.Capture(kind="tick", walked=occ, apps=apps,
                        d_probs=probs, d_edges=edges, ranks=ranks,
                        attained=rows["attained"], occupied=occ)
    kw = dict(seed=SEED, n_walkers=W, n_buckets=NB)
    sound = check.compare_capture(cap, tables, **kw)
    ctl = check.compare_capture(cap, tables, precision="high", **kw)
    limits = load_limits()
    assert sound["walker_moves"] == sound["edge_gap"] == \
        sound["rank_gap"] == 0.0
    assert ctl["rank_gap"] > limits["rank_gap"]
    for k in check.RUN_RULES:
        ctl[k] = sound[k] = 0.0
    assert check.verdict(sound, limits)
    assert not check.verdict(ctl, limits)
    assert ctl["edge_gap"] > limits["edge_gap"]


def test_rows_from_apps_follow_the_reference_layout(kb):
    tables = reference.build_tables(kb, T_IN, T_OUT)
    U, S = tables["samples"].shape[1:]
    name = sorted(kb)[1]
    units = sorted(kb[name].units)
    long = np.arange(S + 7, dtype=np.float32)
    app = {"app": name, "unit": units[-1], "executed": np.float32(1.5),
           "attained": np.float32(9.0), "key_id": 7, "refresh_id": 3,
           "overrides": {units[0]: long, "no-such-unit": long}}
    rows = reference.rows_from_apps(tables, [app, dict(app, overrides={})])
    assert rows["graph"].tolist() == [1, 1]
    assert rows["start"].tolist() == [len(units) - 1] * 2
    ovs, ovc = reference._override_rows(tables, rows["graph"],
                                        rows["overrides"])
    assert ovc[0, 0] == S and ovc[0].sum() == S and not ovc[1].any()
    assert np.array_equal(ovs[0, 0], long[:S])
    assert reference._override_rows(tables, rows["graph"][1:],
                                    rows["overrides"][1:]) == (None, None)


def test_three_pass_keeps_sixteen_bits():
    x = np.asarray([1.0, 273.0, 0.1, 12.345678], np.float32)
    y = reference.three_pass(x)
    assert y[0] == 1.0 and y[1] == 273.0
    rel = np.abs(y - x) / x
    assert rel.max() < 2.0 ** -15 and rel[3] > 0


def _totals(kb, rows):
    p = pack_graphs(kb, T_IN, T_OUT)
    st = walker_streams(np.uint32(SEED), jnp.asarray(rows["key_id"]),
                        jnp.asarray(rows["refresh_id"]))
    rem, _ = pdgraph_walk(p.samples, p.counts, p.cum_trans,
                          jnp.asarray(rows["graph"]),
                          jnp.asarray(rows["start"]),
                          jnp.asarray(rows["executed"]), st,
                          jnp.asarray(rows["ov_samples"]),
                          jnp.asarray(rows["ov_counts"]), n_walkers=W,
                          impl="ref", compact_schedule=())
    return jnp.asarray(rows["attained"])[:, None] + jnp.maximum(rem, 0.0)


def test_triage_follows_the_program(kb):
    """P90 and P10 to the bit, the mean to float32 rounding, over the
    reference walk's own totals; its control is off by more than the
    limit."""
    from repro.core.refresh_pipeline import _triage_stats
    tables, apps, rows = _rows(kb, seed=2)
    want = [np.asarray(x) for x in _triage_stats(_totals(kb, rows))]
    kw = dict(seed=SEED, n_walkers=W, n_buckets=NB, block=7)
    _, _, got = reference.refresh_rows(tables, rows, with_triage=True, **kw)
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])
    assert np.allclose(got[2], want[2], rtol=1e-6, atol=0)
    gap = lambda t: max(check._rel(a, b).max()        # noqa: E731
                        for a, b in zip(t, got))
    assert gap(want) <= load_limits()["triage_gap"]
    _, _, ctl = reference.refresh_rows(tables, rows, with_triage=True,
                                       precision="high", **kw)
    assert gap(ctl) > load_limits()["triage_gap"]


def test_ddl_class_follows_the_policy():
    """The reference's class is the deadline policy's key over 10**6."""
    from repro.core.policies import HermesDDLPolicy
    rng = np.random.default_rng(5)
    n = 400
    opt = rng.uniform(0, 200, n).astype(np.float32)
    sup = (opt + rng.uniform(0, 200, n)).astype(np.float32)
    att = rng.uniform(0, 150, n).astype(np.float32)
    now = 1000.0
    ddl = now + rng.uniform(-100, 400, n)
    ddl[:20] = np.inf
    keys = HermesDDLPolicy().ranks_columns(
        now, g=rng.uniform(0, 50, n).astype(np.float32), sup=sup, opt=opt,
        attained=att, deadline=ddl, mean=opt)
    cls = reference.ddl_class(sup, opt, att, ddl, now)
    assert np.array_equal(np.floor(keys / check.CLASS_SPAN), cls)
    assert set(cls.tolist()) == {0, 1, 2}
    assert (cls[:20] == 1).all()
