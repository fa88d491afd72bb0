"""Event-frontier compaction (ISSUE 7 tentpole) and the ``build`` front
door.

Contracts (DESIGN.md §3 and §8):

* frontier mode is a pure reformulation of the per-edge scans — same
  event order, bit-identical makespans and step counts across graph
  families, netmodels and both flow-slot modes; ``transferred`` agrees
  to 1e-5 relative in frontier+slots mode (per-event f32 accumulation
  order);
* same-timestamp events batch into one step in *both* modes
  (``n_events > n_steps``), so the frontier's win is per-step cost,
  never a step-count change;
* a frontier overflow is honest: ``overflow=True`` and ``ok=False``,
  never silent truncation;
* the deprecated per-graph factories still work but warn, pointing at
  ``build``;
* ``build`` dispatches to the static simulator / static scheduler /
  dynamic simulator and rejects unknown options.
"""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import MiB
from repro.core.graphs import make_graph
from repro.core.imodes import encode_imode
from repro.core.taskgraph import TaskGraph
from repro.core.vectorized import (SimConfig, build, build_for_graph,
                                   encode_graph, make_dynamic_simulator,
                                   make_simulator)
from repro.core.vectorized.scheduling import (bucket_ready_tasks,
                                              frontier_mask,
                                              make_vec_scheduler)
from repro.core.vectorized.specs import (FLOW_FRONTIER_PER_WORKER,
                                         FRONTIER_FLOOR, as_bucketed,
                                         frontier_cap, frontier_caps_for,
                                         frontier_caps_for_spec)

XFER_RTOL = 1e-5      # f32 summation-order tolerance (DESIGN.md §3)


def _spread_assignment(spec, W, cores, seed):
    import random
    rng = random.Random(seed)
    cores_l = [cores] * W if np.isscalar(cores) else list(cores)
    return np.asarray([rng.choice([w for w in range(W)
                                   if cores_l[w] >= int(c)])
                       for c in spec.cpus], np.int32)


def _run_static(g, netmodel, flow_slots, frontier, W=8, cores=4):
    spec = encode_graph(g)
    a = _spread_assignment(spec, W, cores, seed=17)
    p = np.arange(spec.T, 0, -1).astype(np.float32)
    run = jax.jit(build(spec, n_workers=W, cores=cores, netmodel=netmodel,
                        flow_slots=flow_slots, frontier=frontier))
    return run(a, p, bandwidth=np.float32(100 * MiB))


@pytest.mark.parametrize("gname", ["crossv", "merge_triplets", "fork1"])
@pytest.mark.parametrize("netmodel", ["maxmin", "simple"])
@pytest.mark.parametrize("flow_slots", [None, False])
def test_static_frontier_parity(gname, netmodel, flow_slots):
    """3 graph families x 2 netmodels x both flow-slot modes: frontier
    on/off give identical makespans, ok and step counts."""
    g = make_graph(gname, seed=0)
    base = _run_static(g, netmodel, flow_slots, frontier=False)
    front = _run_static(g, netmodel, flow_slots, frontier=True)
    assert bool(base.ok) and bool(front.ok)
    assert not bool(front.overflow)
    assert float(front.makespan) == float(base.makespan)
    assert int(front.n_steps) == int(base.n_steps)
    assert int(front.n_events) == int(base.n_events)
    dev = abs(float(front.transferred) - float(base.transferred))
    assert dev <= XFER_RTOL * max(1.0, abs(float(base.transferred)))


@pytest.mark.parametrize("sched", ["blevel", "greedy"])
def test_dynamic_frontier_parity(sched):
    g = make_graph("crossv", seed=0)
    spec = encode_graph(g)
    runs = {fr: jax.jit(build(spec, n_workers=8, cores=4, scheduler=sched,
                              dynamic=True, frontier=fr))
            for fr in (False, True)}
    for msd, dd, im in [(0.0, 0.0, "exact"), (0.1, 0.05, "user")]:
        d, s = encode_imode(g, im)
        res = {fr: run(d, s, np.float32(msd), np.float32(dd))
               for fr, run in runs.items()}
        assert bool(res[False].ok) and bool(res[True].ok), (msd, dd, im)
        assert float(res[True].makespan) == float(res[False].makespan)
        assert int(res[True].n_steps) == int(res[False].n_steps)
        dev = abs(float(res[True].transferred)
                  - float(res[False].transferred))
        assert dev <= XFER_RTOL * max(
            1.0, abs(float(res[False].transferred))), (msd, dd, im)


def wide_fork(n=12):
    """One root fanning out to ``n`` equal-duration children: all the
    children finish at the same timestamp."""
    g = TaskGraph("wide_fork")
    root = g.new_task(1.0, outputs=[10 * MiB], expected_duration=1.0,
                      expected_sizes=[10 * MiB], name="root")
    for _ in range(n):
        g.new_task(2.0, inputs=root.outputs, expected_duration=2.0,
                   name="child")
    return g


def test_same_timestamp_events_batch_in_both_modes():
    """The n children end together => far fewer steps than events, and
    the frontier mode batches exactly like the baseline (its win is
    per-step cost, not step count)."""
    g = wide_fork(12)
    res = {fr: _run_static(g, "maxmin", None, fr, W=16, cores=4)
           for fr in (False, True)}
    for fr, r in res.items():
        assert bool(r.ok), fr
        assert int(r.n_events) > int(r.n_steps)
    assert int(res[True].n_steps) == int(res[False].n_steps)
    assert int(res[True].n_events) == int(res[False].n_events)


def independent_tasks(n=24):
    g = TaskGraph("independent")
    for i in range(n):
        g.new_task(1.0 + 0.01 * i, expected_duration=1.0 + 0.01 * i,
                   name="t")
    return g


def test_frontier_overflow_is_honest():
    """More simultaneously-enabled tasks than the task frontier holds:
    the run must flag overflow and poison ok, never silently drop."""
    g = independent_tasks(24)
    spec = encode_graph(g)
    a = np.zeros(spec.T, np.int32)
    p = np.arange(spec.T, 0, -1).astype(np.float32)
    run = jax.jit(build(spec, n_workers=2, cores=2, frontier=True,
                        frontier_caps=(4, 4)))
    res = run(a, p)
    assert bool(res.overflow)
    assert not bool(res.ok)
    # same shape with ample caps stays clean
    ok_run = jax.jit(build(spec, n_workers=2, cores=2, frontier=True))
    res2 = ok_run(a, p)
    assert bool(res2.ok) and not bool(res2.overflow)


def test_root_aware_caps_cover_all_roots_graphs():
    """A graph whose simultaneously-ready root set exceeds the
    shape-derived task cap (duration_stairs: 380 independent roots vs
    cap 256) must still run clean through ``build`` — the concrete-spec
    path widens the cap to the root count (specs.frontier_caps_for_spec)."""
    g = make_graph("duration_stairs", seed=0)
    spec = encode_graph(g)
    bspec = as_bucketed(spec)
    cf_shape, ct_shape = frontier_caps_for(bspec.shape, n_workers=8)
    cf, ct = frontier_caps_for_spec(bspec, n_workers=8)
    n_roots = int(np.sum(np.asarray(bspec.n_inputs) == 0))
    assert n_roots > ct_shape          # the shape-only cap would overflow
    assert cf == cf_shape and ct_shape < ct <= spec.T and ct >= n_roots
    res = _run_static(g, "maxmin", None, frontier=True)
    assert bool(res.ok) and not bool(res.overflow)


def test_frontier_cap_derivation():
    assert frontier_cap(0) == 0
    assert frontier_cap(96) == 96                  # full coverage
    assert frontier_cap(FRONTIER_FLOOR) == FRONTIER_FLOOR
    big = frontier_cap(2048)
    assert FRONTIER_FLOOR <= big < 2048
    # the simlint JX106 shape: caps distinct from every axis
    assert frontier_caps_for((1280, 192, 2048), n_workers=4) == (512, 320)
    cf, ct = frontier_caps_for((2048, 576, 2016), n_workers=4)
    assert ct == frontier_cap(2048) and cf == frontier_cap(2016)
    assert (cf, ct) == (512, 512)          # the T2048 bench caps


@pytest.mark.parametrize("W,want_cf", [(8, 256), (16, 256), (32, 512),
                                       (128, 992)])
def test_flow_cap_grows_with_workers(W, want_cf):
    """Pending transfers grow with the destination count: the flow cap
    holds ``FLOW_FRONTIER_PER_WORKER`` per worker, never beyond E; the
    task cap does not depend on W."""
    cf, ct = frontier_caps_for((512, 320, 992), n_workers=W)
    assert cf == want_cf and ct == frontier_cap(512)
    assert cf >= min(992, FLOW_FRONTIER_PER_WORKER * W)


def test_w32_survey_graph_fits_the_flow_frontier():
    """crossvx on the paper's 32x4 cluster in the T512 bucket under
    blevel/maxmin overflowed the shape-only flow cap (256); the
    worker-scaled cap holds it, so the runner does not raise."""
    from repro.core import MiB
    from repro.core.vectorized import BucketedGridRunner

    runner = BucketedGridRunner([(make_graph("crossvx", seed=0), None)],
                                "blevel", 32, 4, shape=(512, 320, 992))
    ms, _ = runner([dict(imode="exact", bandwidth=32 * MiB, msd=0.0,
                         decision_delay=0.0)])
    assert np.isfinite(ms).all()


def test_frontier_mask_and_bucket_ready_tasks():
    g = make_graph("crossv", seed=0)
    bspec = as_bucketed(encode_graph(g))
    m = np.asarray(frontier_mask(jnp.asarray([3, -1, 0, 3], jnp.int32), 6))
    assert m.tolist() == [True, False, False, True, False, False]
    # frontier path == dense recompute for the all-roots-done state
    t_done = np.asarray(bspec.n_inputs) == 0
    dense = bucket_ready_tasks(bspec, t_done=jnp.asarray(t_done))
    ready_ids = np.flatnonzero(np.asarray(dense)).astype(np.int32)
    fr = np.full(max(8, len(ready_ids)), -1, np.int32)
    fr[:len(ready_ids)] = ready_ids
    via_frontier = bucket_ready_tasks(bspec, frontier=jnp.asarray(fr))
    np.testing.assert_array_equal(np.asarray(via_frontier),
                                  np.asarray(dense))
    with pytest.raises(ValueError, match="t_done"):
        bucket_ready_tasks(bspec)


def test_deprecated_factories_warn_and_point_at_build():
    g = make_graph("fork1", seed=0)
    spec = encode_graph(g)
    with pytest.warns(DeprecationWarning, match="build"):
        make_simulator(spec, 4, 4)
    with pytest.warns(DeprecationWarning, match="build"):
        make_dynamic_simulator(spec, 4, 4)
    with pytest.warns(DeprecationWarning, match="build"):
        make_vec_scheduler(spec, 4, 4, "blevel")
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        build(spec, n_workers=4, cores=4)       # the replacement: silent


def test_build_dispatch_and_simresult():
    g = make_graph("fork1", seed=0)
    spec = encode_graph(g)
    d, s = encode_imode(g, "exact")
    # static scheduler form
    sched = build(spec, n_workers=4, cores=4, scheduler="blevel")
    a, p = jax.jit(sched)(d, s, np.float32(100 * MiB))
    assert a.shape == p.shape and a.shape[0] >= spec.T  # bucket-padded
    # static simulator form -> SimResult
    res = jax.jit(build(spec, n_workers=4, cores=4))(np.asarray(a), p)
    for field in ("makespan", "transferred", "ok", "overflow",
                  "n_events", "n_steps"):
        assert hasattr(res, field), field
    assert bool(res.ok)
    # dynamic form with config defaults baked in
    dyn = build(spec, n_workers=4, cores=4, scheduler="blevel",
                dynamic=True, config=SimConfig(msd=0.1))
    res_d = jax.jit(dyn)(d, s)
    assert bool(res_d.ok)
    # graph-level convenience
    res_g = jax.jit(build_for_graph(g, n_workers=4, cores=4))(
        np.asarray(a), p)
    assert float(res_g.makespan) == float(res.makespan)


def test_build_rejects_unknown_options_and_guards_cpus():
    g = make_graph("fork1", seed=0)
    spec = encode_graph(g)
    with pytest.raises(TypeError, match="unknown option"):
        build(spec, n_workers=4, cores=4, frontier_size=7)
    cfg = SimConfig(frontier=False)
    assert cfg.replace(frontier=True).frontier is True
    with pytest.raises(Exception):
        cfg.frontier = True                      # frozen
    import test_vectorized_dynamic as tvd
    with pytest.raises(ValueError, match="largest worker"):
        build(encode_graph(tvd.mini_cpus()), n_workers=3, cores=[1, 1, 1])
