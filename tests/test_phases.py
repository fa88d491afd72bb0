"""The program's own phases: the dynamic event loop's named scopes
(``SIM_PHASES``: four in order, and the flow frontier's backlog inside
``sim.ready``), which the compiler keeps in each op's ``op_name`` so a
device trace can charge the op to its phase, and set-up seconds by
phase on the host (``setup_seconds`` / ``setup_timer``), counted once
however JAX's compile steps and the runner's own spans nest."""
import time

import jax
import numpy as np
import pytest

from repro.core import MiB
from repro.core.imodes import encode_imode
from repro.core.vectorized import (SETUP_PHASES, SIM_PHASES,
                                   BucketedGridRunner, as_bucketed,
                                   encode_graph,
                                   make_bucket_dynamic_simulator,
                                   setup_seconds, setup_timer)
from repro.core.vectorized.engine import setup_span

import test_vectorized_dynamic as tvd

POINTS = [dict(imode="exact", bandwidth=32 * MiB, msd=0.0,
               decision_delay=0.0),
          dict(imode="user", bandwidth=100 * MiB, msd=0.1,
               decision_delay=0.05)]


@pytest.mark.parametrize("frontier", [None, False])
@pytest.mark.parametrize("netmodel", ["maxmin", "simple"])
@pytest.mark.parametrize("scheduler", ["blevel", "greedy"])
def test_loop_phases_are_named_in_the_lowering(scheduler, netmodel,
                                               frontier):
    g = tvd.mini_fork()
    d, s = encode_imode(g, "user")

    def lowered(frontier_caps=None):
        run = make_bucket_dynamic_simulator(4, 2, scheduler=scheduler,
                                            netmodel=netmodel,
                                            frontier=frontier,
                                            frontier_caps=frontier_caps)
        return jax.jit(run).lower(as_bucketed(encode_graph(g)), d, s,
                                  np.float32(0.1), np.float32(0.05)
                                  ).as_text(debug_info=True)

    text = lowered()
    schedule, ready, rates, advance, backlog = SIM_PHASES
    for phase in (schedule, ready, rates, advance):
        assert f"while/body/{phase}/" in text, phase
    # the flow frontier's backlog runs inside sim.ready, in a loop of at
    # most one trip, where a max-min flow list narrower than E keeps one;
    # this graph's derived list holds all of E
    nested = f"while/body/{ready}/while/body/{backlog}/"
    assert backlog not in text
    assert (nested in lowered((4, 32))) == (netmodel == "maxmin"
                                            and frontier is None)
    # the schedule before the loop: blevel's order and placement, or
    # greedy's priorities
    assert f"/{SIM_PHASES[0]}/" in text.replace("while/body/", "")


def test_first_call_splits_its_setup_and_a_warm_call_adds_none():
    g1, g2 = tvd.mini_fork(), tvd.mini_merge()
    t0 = time.perf_counter()
    with setup_timer() as first:
        runner = BucketedGridRunner([(g1, None), (g2, None)], "greedy", 4,
                                    2)
        runner(POINTS)
    wall = time.perf_counter() - t0
    seconds = first.seconds
    assert set(seconds) == set(SETUP_PHASES)
    assert seconds["trace"] > 0 and seconds["host"] > 0
    assert sum(seconds.values()) <= wall        # no second counted twice
    with setup_timer() as warm:
        runner(POINTS)
    assert warm.seconds["trace"] == 0 and warm.seconds["compile"] == 0


def test_setup_timers_nest_and_nested_steps_count_once():
    before = setup_seconds()
    with setup_timer() as outer:
        with setup_span("host"):
            with setup_span("compile"):     # inside an open step
                time.sleep(0.01)
            jax.jit(lambda x: x * 3).lower(1.0)   # JAX's own, inside too
        with setup_timer() as inner:
            with setup_span("compile"):
                time.sleep(0.01)
    assert inner.seconds["compile"] >= 0.01
    assert inner.seconds["host"] == inner.seconds["trace"] == 0
    assert outer.seconds["host"] >= 0.01 and outer.seconds["trace"] == 0
    assert outer.seconds["compile"] == inner.seconds["compile"]
    after = setup_seconds()
    assert {p: after[p] - before[p] for p in SETUP_PHASES} == \
        outer.seconds


def test_a_failed_step_still_closes():
    with setup_timer() as st:
        with pytest.raises(RuntimeError), setup_span("host"):
            raise RuntimeError("step failed")
        with setup_span("compile"):
            time.sleep(0.005)
    assert st.seconds["host"] > 0 and st.seconds["compile"] >= 0.005
    with pytest.raises(KeyError, match="set-up phase"):
        with setup_span("warm-up"):
            pass
