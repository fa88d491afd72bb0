"""Shape-bucketed padded batches + heterogeneous clusters (DESIGN.md §3).

Three contracts from the bucketing refactor:

* padding is semantically inert — a graph padded into a larger shape
  bucket produces the same makespans and transferred bytes as the
  unpadded per-graph path, to float32 tolerance;
* one jit compilation serves a whole bucket (``trace_counter``);
* heterogeneous per-worker core lists (incl. zero-core padded workers)
  match the reference simulator under the existing parity tolerances.
"""
import numpy as np
import pytest

from repro.core import MiB, make_scheduler, parse_cluster, Simulator
from repro.core.simulator import resolve_workers
from repro.core.graphs import make_graph, survey_names, encode_graph_batch
from repro.core.vectorized import (encode_graph, pad_spec, pad_specs,
                                   stack_specs, t_bucket, bucket_shape,
                                   BucketedGridRunner, DynamicGridRunner,
                                   trace_counter)

import test_vectorized_dynamic as tvd

POINTS = [dict(imode=im, bandwidth=bw * MiB, msd=m,
               decision_delay=0.05 if m > 0 else 0.0, seed=3)
          for im in ("exact", "user") for bw in (32, 100)
          for m in (0.0, 0.1)]


def test_parse_cluster():
    assert parse_cluster("8x4") == [4] * 8
    assert parse_cluster("1x8+4x2") == [8, 2, 2, 2, 2]
    assert parse_cluster("2x4+1x1+1x2") == [4, 4, 1, 2]
    with pytest.raises(ValueError):
        parse_cluster("")


def test_t_bucket_and_bucket_shape():
    assert t_bucket(1) == 32 and t_bucket(32) == 32
    assert t_bucket(33) == 160 and t_bucket(148) == 160
    assert t_bucket(161) == 512
    assert t_bucket(3000) == 4096          # beyond the last edge
    s1 = encode_graph(make_graph("fastcrossv", seed=0))   # T=88 E=406
    s2 = encode_graph(make_graph("sipht", seed=0))        # T=64 O=136
    T, O, E = bucket_shape([s1, s2])
    assert T == 160 and O >= max(s1.O, s2.O) and E >= max(s1.E, s2.E)
    assert O % 32 == 0 and E % 32 == 0


def test_pad_specs_masks_and_grouping():
    specs = {n: encode_graph(make_graph(n, seed=0))
             for n in survey_names(2)}
    groups = pad_specs(specs)
    assert sum(len(g.names) for g in groups) == len(specs)
    for grp in groups:
        T, O, E = grp.shape
        b = grp.batch
        assert b.durations.shape == (len(grp.names), T)
        for i, name in enumerate(grp.names):
            spec = specs[name]
            assert int(b.task_valid[i].sum()) == spec.T
            assert int(b.obj_valid[i].sum()) == spec.O
            assert int(b.edge_valid[i].sum()) == spec.E
            # inert filler: zero durations/sizes beyond the real prefix
            assert not b.durations[i, spec.T:].any()
            assert not b.sizes[i, spec.O:].any()
    # members of one group share a T bucket
    for grp in groups:
        for s in grp.specs:
            assert t_bucket(s.T) == grp.shape[0]


def test_stack_specs_rejects_mixed_shapes():
    s = encode_graph(make_graph("sipht", seed=0))
    with pytest.raises(ValueError):
        stack_specs([pad_spec(s, (160, 160, 96)),
                     pad_spec(s, (512, 160, 96))])


@pytest.mark.parametrize("gname", list(tvd.GRAPHS))
@pytest.mark.parametrize("sched", ["blevel", "etf", "greedy"])
def test_padding_is_inert(gname, sched):
    """A single graph padded deep into a larger bucket must reproduce
    the unpadded vectorized results (near-bitwise: the same program on
    inert extra entries)."""
    make, W, cores = tvd.GRAPHS[gname]
    g = make()
    spec = encode_graph(g)
    shape = (t_bucket(spec.T + 5), 32 * ((spec.O + 37) // 32 + 1),
             32 * ((spec.E + 61) // 32 + 1))
    bucket = BucketedGridRunner([(g, spec)], sched, W, cores, shape=shape)
    plain = DynamicGridRunner(g, sched, W, cores, spec=spec)
    ms_b, xf_b = bucket(POINTS)
    ms_p, xf_p = plain(POINTS)
    np.testing.assert_allclose(ms_b[0], ms_p, rtol=1e-6)
    np.testing.assert_allclose(xf_b[0], xf_p, rtol=1e-6)


@pytest.mark.parametrize("sched", ["blevel", "random"])
def test_bucketed_batch_matches_per_graph_survey_reps(sched):
    """The survey representatives batched through one bucket equal the
    per-graph vectorized path (the acceptance grid of ISSUE 3)."""
    names = survey_names(1)
    encoded, groups = encode_graph_batch(names, seed=0, bucket=True)
    assert len(groups) == 1          # all reps share the T160 bucket
    grp = groups[0]
    pts = POINTS[:4]
    bucket = BucketedGridRunner([encoded[n] for n in grp.names], sched,
                                8, 4, shape=grp.shape)
    ms_b, xf_b = bucket(pts)
    for b, name in enumerate(grp.names):
        g, spec = encoded[name]
        ms_p, xf_p = DynamicGridRunner(g, sched, 8, 4, spec=spec)(pts)
        np.testing.assert_allclose(ms_b[b], ms_p, rtol=1e-5,
                                   err_msg=f"{name}/{sched}")
        np.testing.assert_allclose(xf_b[b], xf_p, rtol=1e-5,
                                   err_msg=f"{name}/{sched}")


def test_one_compile_serves_a_bucket():
    """Compile-count regression gate: a two-graph bucket costs exactly
    one jit trace, and warm calls cost none (scoped ``trace_counter``,
    so parallel test files can't bleed into the delta)."""
    g1, g2 = tvd.mini_fork(), tvd.mini_merge()
    with trace_counter() as tc:
        runner = BucketedGridRunner([(g1, None), (g2, None)], "blevel", 4, 2)
        ms, _ = runner(POINTS[:2])
        assert tc.count == 1
        assert ms.shape == (2, 2) and np.isfinite(ms).all()
        runner(POINTS[:2])
    assert tc.count == 1                     # warm call: no retrace


def test_trace_count_reset_and_nesting():
    """``trace_counter`` reads deltas of the process odometer, so nested
    scopes each count the traces inside them and a scope opened after
    the fact counts none of them."""
    g = tvd.mini_fork()
    with trace_counter() as outer:
        with trace_counter() as inner:
            BucketedGridRunner([(g, None)], "blevel", 4, 2)(POINTS[:1])
        assert inner.count == 1
        with trace_counter() as later:
            pass
        assert later.count == 0
    assert outer.count == 1 and inner.count == 1


@pytest.mark.parametrize("cluster", ["1x4+3x2", "2x4+2x1"])
@pytest.mark.parametrize("vec_sched,ref_sched",
                         [("blevel", "blevel-det"), ("etf", "etf-det"),
                          ("greedy", "greedy")])
@pytest.mark.parametrize("netmodel", ["maxmin", "simple"])
def test_hetero_cluster_matches_reference(cluster, vec_sched, ref_sched,
                                          netmodel):
    """Reference-vs-vectorized parity on per-worker core lists: mixed
    cores across >= 2 schedulers and both netmodels (the ISSUE 3
    satellite; tolerances as in the homogeneous parity suite)."""
    cores = parse_cluster(cluster)
    g = tvd.mini_cpus()
    pts = [dict(msd=m, decision_delay=d, imode=im, bandwidth=100 * MiB)
           for m in (0.0, 0.1) for d in (0.0, 0.05)
           for im in ("exact", "user")]
    ms, xf = DynamicGridRunner(g, vec_sched, len(cores), cores,
                               netmodel=netmodel)(pts)
    for p, m, x in zip(pts, ms, xf):
        sched = make_scheduler(ref_sched, seed=0)
        rep = Simulator(g, resolve_workers(list(cores)), sched,
                        netmodel=netmodel, bandwidth=p["bandwidth"],
                        imode=p["imode"], msd=p["msd"],
                        decision_delay=p["decision_delay"]).run()
        label = f"{cluster}/{vec_sched}/{netmodel}/{p}"
        assert float(m) == pytest.approx(rep.makespan, rel=2e-3), label
        assert float(x) == pytest.approx(rep.transferred_bytes,
                                         rel=1e-3, abs=1.0), label


def test_zero_core_padded_workers_are_inert():
    """A cluster padded with zero-core workers behaves exactly like the
    unpadded cluster — the cores vector's padding story."""
    g = tvd.mini_merge()
    pts = POINTS[:4]
    ms_a, xf_a = DynamicGridRunner(g, "blevel", 4, [4, 2, 2, 1])(pts)
    ms_b, xf_b = DynamicGridRunner(g, "blevel", 6,
                                   [4, 2, 2, 1, 0, 0])(pts)
    np.testing.assert_allclose(ms_a, ms_b, rtol=1e-6)
    np.testing.assert_allclose(xf_a, xf_b, rtol=1e-6)


def test_hetero_cluster_in_bucketed_runner():
    """Heterogeneous cores vector + padded bucket batch compose: the
    bucketed hetero run equals the per-graph hetero run."""
    cores = parse_cluster("1x8+4x2")
    g1, g2 = tvd.mini_fork(), tvd.mini_merge()
    pts = POINTS[:4]
    bucket = BucketedGridRunner([(g1, None), (g2, None)], "greedy",
                                len(cores), cores)
    ms_b, xf_b = bucket(pts)
    for b, g in enumerate((g1, g2)):
        ms_p, xf_p = DynamicGridRunner(g, "greedy", len(cores), cores)(pts)
        np.testing.assert_allclose(ms_b[b], ms_p, rtol=1e-6)
        np.testing.assert_allclose(xf_b[b], xf_p, rtol=1e-6)


def test_cpus_guard_against_small_hetero_cluster():
    """Tasks that fit no worker raise host-side (mirrors the reference
    scheduler guard), also through the bucketed path."""
    g = tvd.mini_cpus()              # has 2-core tasks
    with pytest.raises(ValueError, match="largest worker"):
        DynamicGridRunner(g, "blevel", 3, [1, 1, 1])
    with pytest.raises(ValueError, match="largest worker"):
        BucketedGridRunner([(g, None)], "blevel", 3, [1, 1, 1])
