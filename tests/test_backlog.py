"""The flow frontier's backlog (DESIGN.md §3): a wanted download that
finds the bounded list of candidate flows full waits outside it and is
offered again, where it used to poison ``ok``.  While any key waits, the
pick runs over every edge, so results stay those of a list that held
every key: the same makespans, bytes, steps and events, and the
reference's.  The task frontier still fails loudly
(``test_frontier.py``).  An iteration with no waiting key adds a
predicate to ``sim.ready``; the backlog's own ops run in a loop of at
most one trip, named ``sim.backlog``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MiB, Simulator, TaskGraph, make_scheduler
from repro.core.graphs import make_graph
from repro.core.imodes import encode_imode
from repro.core.schedulers.fixed import FixedScheduler
from repro.core.simulator import resolve_workers
from repro.core.vectorized import (SIM_PHASES, as_bucketed, build,
                                   encode_graph,
                                   make_bucket_dynamic_simulator,
                                   make_static_blevel_scheduler)
from repro.core.vectorized.sim import _run_once

import test_vectorized_dynamic as tvd
from test_pick_rounds import _CALL, _FUNC, _LOC, _OP_LOC

BW = 32 * MiB
REL = 1e-5           # f32 against the float64 reference
SHUFFLE = "mapreduce-17-s0"     # 8 maps x 8 reduces + 1 collector


def same(a, b):
    """Bit-equal results, the backlog's peak aside."""
    for f in ("makespan", "transferred", "ok", "overflow", "n_events",
              "n_steps"):
        assert np.asarray(getattr(a, f)).tobytes() == \
            np.asarray(getattr(b, f)).tobytes(), f


def reference(g, sched, W, cores, msd=0.0, delay=0.0):
    return Simulator(g, resolve_workers([cores] * W), sched,
                     netmodel="maxmin", bandwidth=BW, imode="exact",
                     msd=msd, decision_delay=delay).run()


def close(res, rep):
    assert bool(res.ok) and not bool(res.overflow)
    assert float(res.makespan) == pytest.approx(rep.makespan, rel=REL)
    assert float(res.transferred) == pytest.approx(rep.transferred_bytes,
                                                   rel=REL)


@pytest.mark.parametrize("msd,delay", [(0.0, 0.0), (0.1, 0.05)])
@pytest.mark.parametrize("sched,twin", [("greedy", "greedy"),
                                        ("blevel", "blevel-det")])
def test_small_shuffle_waits_and_agrees_with_the_reference(sched, twin, msd,
                                                           delay):
    """Dynamic loop, W = 4: a flow list of 4 entries (and of none) holds
    a fraction of the shuffle's keys; the rest wait, and the simulation
    is the one a list of every key gives, and the reference's."""
    g = make_graph(SHUFFLE)
    spec = encode_graph(g)
    d, s = encode_imode(g, "exact")
    res = {}
    for caps in (None, (4, spec.T), (0, spec.T)):
        run = jax.jit(build(spec, n_workers=4, cores=2, scheduler=sched,
                            dynamic=True, frontier_caps=caps))
        res[caps] = run(d, s, np.float32(msd), np.float32(delay),
                        np.float32(BW))
    assert int(res[None].backlog_peak) == 0        # the list held them all
    for caps in ((4, spec.T), (0, spec.T)):
        assert int(res[caps].backlog_peak) > 0, caps
        same(res[caps], res[None])
    close(res[(4, spec.T)], reference(g, make_scheduler(twin), 4, 2, msd,
                                      delay))


@pytest.mark.parametrize("flow_slots", [None, False])
def test_static_loop_offers_waiting_keys_again(flow_slots):
    """Static loop: a flow is a candidate once its producer finishes, so
    a key that did not fit is carried as waiting and offered again; the
    in-loop blevel schedule replayed with a tiny list equals the
    per-edge baseline and the reference."""
    g = make_graph(SHUFFLE)
    spec = encode_graph(g)
    d, s = encode_imode(g, "exact")
    aw, prio = jax.jit(make_static_blevel_scheduler(spec, 4, 2))(
        d, s, np.float32(BW))
    res = {}
    for frontier, caps in ((False, None), (True, None), (True, (1, spec.T)),
                           (True, (0, spec.T))):
        run = jax.jit(build(spec, n_workers=4, cores=2, flow_slots=flow_slots,
                            frontier=frontier, frontier_caps=caps))
        res[frontier, caps] = run(aw, prio, bandwidth=np.float32(BW))
    base = res[False, None]
    assert int(res[True, None].backlog_peak) == 0
    for caps in ((1, spec.T), (0, spec.T)):
        assert int(res[True, caps].backlog_peak) > 0, caps
        same(res[True, caps], base)
    close(base, reference(g, make_scheduler("blevel-det"), 4, 2))


def burst():
    """Worker 0's task ``a`` sends eight shards to eight consumers on
    worker 1, while worker 2's ``b`` sends one to ``d`` on worker 0, at
    the same instant.  The consumers' edges come first, so a list of 4
    fills with worker 1's keys and ``d``'s waits, though worker 0 has
    every download slot free.  ``d`` is the longest task, so the
    makespan shows when its download started."""
    g = TaskGraph("burst")
    a = g.new_task(1.0, outputs=[(10 + i) * MiB for i in range(8)],
                   name="a")
    b = g.new_task(1.0, outputs=[12.5 * MiB], name="b")
    for i in range(8):
        g.new_task(0.5 + 0.01 * i, inputs=[a.outputs[i]], name="c")
    g.new_task(20.0, inputs=b.outputs, name="d")
    where = [0, 2] + [1] * 8 + [0]
    return g, where


@pytest.mark.parametrize("flow_slots", [None, False])
def test_one_destinations_burst_does_not_hold_back_another(flow_slots):
    g, where = burst()
    spec = encode_graph(g)
    n = len(g.tasks)
    prio = np.arange(n, 0, -1).astype(np.float32)
    res = {}
    for caps in (None, (4, spec.T)):
        run = jax.jit(build(spec, n_workers=3, cores=1, flow_slots=flow_slots,
                            frontier_caps=caps))
        res[caps] = run(np.asarray(where, np.int32), prio,
                        bandwidth=np.float32(BW))
    # 9 keys at once: 4 fit, 5 wait, d's among them
    assert int(res[(4, spec.T)].backlog_peak) == 5
    same(res[(4, spec.T)], res[None])
    rep = reference(g, FixedScheduler(dict(zip(g.tasks, where)),
                                      dict(zip(g.tasks, prio.tolist()))),
                    3, 1)
    close(res[(4, spec.T)], rep)


@pytest.mark.parametrize("dynamic", [False, True])
def test_backlog_peak_is_zero_where_the_list_holds_every_key(dynamic):
    g = tvd.mini_merge()
    spec = encode_graph(g)
    if dynamic:
        d, s = encode_imode(g, "user")
        res = jax.jit(build(spec, n_workers=4, cores=2, scheduler="greedy",
                            dynamic=True))(d, s)
    else:
        run = jax.jit(build(spec, n_workers=4, cores=2))
        res = run(np.arange(spec.T, dtype=np.int32) % 4,
                  np.arange(spec.T, 0, -1).astype(np.float32))
    assert bool(res.ok) and int(res.backlog_peak) == 0


def test_run_once_runs_only_the_lanes_that_need_it():
    def bump(c):
        return dict(c, x=c["x"] + 1)

    preds = jnp.asarray([True, False, True])
    got = jax.vmap(lambda p, x: _run_once(p, bump, {"x": x}))(
        preds, jnp.zeros(3, jnp.int32))
    assert got["x"].tolist() == [1, 0, 1]
    none = jax.vmap(lambda p, x: _run_once(p, bump, {"x": x}))(
        jnp.zeros(3, bool), jnp.arange(3))
    assert none["x"].tolist() == [0, 1, 2]


# ------------------------------------------------------ the lowering

# sim.ready's ops for blevel/max-min at W = 4 (mini_fork) before the
# backlog, constants aside, and what it may add to them: 2 where the
# flow list holds all of E (the task list's count of entries left out),
# and 10 with a list of 4 entries (besides, the predicate on the count,
# the picks' mask, the peak and the loop of at most one trip)
READY_OPS_BEFORE = 1216
READY_OPS_ADDED = {None: 2, (4, 32): 10}


def ops_under(text, scope, exclude=None):
    """The ops of a lowered module (``as_text(debug_info=True)``),
    constants and returns aside, whose location lies under ``scope`` and
    not under ``exclude``, counting those inside the private functions
    that such an op calls."""
    locs = dict(_LOC.findall(text))
    funcs, cur = {}, None
    for line in text.splitlines():
        m = _FUNC.match(line)
        if m:
            cur = m.group(1)
            funcs[cur] = []
        if cur is not None:
            funcs[cur].append(line)

    def is_op(line):
        return ("stablehlo." in line and "stablehlo.constant" not in line
                and "stablehlo.return" not in line)

    def total(fn, seen=()):
        n = 0
        for line in funcs.get(fn, []):
            n += is_op(line)
            m = _CALL.search(line)
            if m and m.group(1) not in seen:
                n += total(m.group(1), seen + (fn,))
        return n

    n = 0
    for lines in funcs.values():
        for line in lines:
            m = _OP_LOC.search(line)
            where = locs.get(m.group(1), "") if m else ""
            if scope not in where or (exclude and exclude in where):
                continue
            n += is_op(line)
            call = _CALL.search(line)
            if call:
                n += total(call.group(1))
    return n


@pytest.mark.parametrize("scheduler,netmodel,caps",
                         [("blevel", "maxmin", None),
                          ("blevel", "maxmin", (4, 32)),
                          ("greedy", "simple", (4, 32))])
def test_sim_ready_gains_a_predicate_and_the_backlog_a_one_trip_loop(
        scheduler, netmodel, caps):
    g = tvd.mini_fork()
    d, s = encode_imode(g, "user")
    run = make_bucket_dynamic_simulator(4, 2, scheduler=scheduler,
                                        netmodel=netmodel,
                                        frontier_caps=caps)
    text = jax.jit(run).lower(as_bucketed(encode_graph(g)), d, s,
                              np.float32(0.1), np.float32(0.05)
                              ).as_text(debug_info=True)
    ready, backlog = SIM_PHASES[1], SIM_PHASES[4]
    outside = ops_under(text, f"while/body/{ready}/", exclude=backlog)
    inside = ops_under(text, f"/{backlog}/")
    if netmodel == "simple" or caps is None:
        # no flow list, or one that holds every key: no backlog
        assert inside == 0
        if caps is None:
            assert outside == READY_OPS_BEFORE + READY_OPS_ADDED[caps]
        return
    assert outside <= READY_OPS_BEFORE + READY_OPS_ADDED[caps], outside
    # every backlog op sits in the one-trip loop inside sim.ready
    assert inside > 0
    assert inside == ops_under(
        text, f"while/body/{ready}/while/body/{backlog}/")
