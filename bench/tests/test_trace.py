"""The reduction from a profiler trace to busy time, idle gaps and kernel
time, on made-up events and on a small trace recorded on a TPU v5e."""
import os

import pytest

from bench import peaks, trace, work
from bench.trace import Event

DEV, HOST = "/device:TPU:0", trace.HOST_PLANE
RECORDED = os.path.join(os.path.dirname(os.path.dirname(__file__)), "data",
                        "v5e_t160_blevel_maxmin.json.gz")


def test_union_merges_overlaps_and_nesting():
    assert trace.union([(5, 7), (0, 2), (1, 3), (6, 6.5)]) == [(0, 3),
                                                                (5, 7)]


def test_reduce_made_up_window():
    ev = [Event(HOST, "python", trace.WINDOW_SPAN, 0, 100),
          Event(HOST, "python", "request.prep", 0, 10),
          Event(HOST, "python", "request.dispatch", 10, 5),
          Event(HOST, "python", "request.readback", 90, 10),
          Event(DEV, trace.OPS_LINE, "while", 15, 70),
          Event(DEV, trace.OPS_LINE, "%waterfill_batch.5", 20, 30),
          Event(DEV, trace.OPS_LINE, "fusion.1", 60, 20),
          Event(DEV, trace.OPS_LINE, "fusion.1", 150, 20)]   # after window
    s = trace.reduce(ev)
    assert s.window_ns == 100 and s.n_devices == 1
    assert s.busy_ns == 70
    assert dict(s.idle_by_host) == {"request.prep": 10,
                                    "request.dispatch": 5,
                                    "request.readback": 10, "other": 5}
    assert trace.kernel(s, "%waterfill_batch") == (1, 30)
    assert dict(s.device_ops) == {"while": 20, "%waterfill_batch.5": 30,
                                  "fusion.1": 20}
    assert trace.kernel(s, "no-such-op") is None


def test_a_truncated_trace_is_refused():
    ev = [Event(HOST, "python", trace.WINDOW_SPAN, 0, 100),
          Event(HOST, "python", "request.wait", 5, 90),
          Event(DEV, trace.OPS_LINE, "op", 5, 60)]
    with pytest.raises(ValueError, match="dropped events"):
        trace.reduce(ev)


def test_a_chip_that_finishes_its_shard_early_is_not_a_truncation():
    ev = [Event(HOST, "python", trace.WINDOW_SPAN, 0, 100),
          Event(HOST, "python", "request.wait", 5, 90),
          Event(DEV, trace.OPS_LINE, "op", 5, 90),
          Event("/device:TPU:1", trace.OPS_LINE, "op", 5, 60)]
    s = trace.reduce(ev)
    assert s.n_devices == 2 and s.busy_ns == 75


def test_idle_share_averages_over_chips():
    ev = [Event(HOST, "python", trace.WINDOW_SPAN, 0, 100),
          Event(DEV, trace.OPS_LINE, "op", 0, 100),
          Event("/device:TPU:1", trace.OPS_LINE, "op", 0, 50)]
    s = trace.reduce(ev)
    assert s.n_devices == 2 and s.busy_ns == 75
    assert dict(s.idle_by_host) == {"other": 25}


def test_load_events_reads_a_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("request.prep"):
            f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.load_events(str(tmp_path))
    names = {e.name for e in ev if e.plane == HOST}
    assert {trace.WINDOW_SPAN, "request.prep"} <= names
    lo, hi = trace.window_of(ev)
    assert hi > lo


def test_roofline_work_counts_shapes_only():
    flops, nbytes = work.waterfill_work(lanes=48, workers=32)
    assert flops == 48 * 2 * 128 * 64
    assert nbytes == 48 * 4 * (3 * 128 + 64 + 128)
    t, bound = work.least_time_s(flops, nbytes, peaks.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_recorded_chip_trace():
    """49 ms of a `t160-blevel-maxmin` window on one v5e around the end
    of a request: the loop's last ops, the readback, the next request's
    prep and dispatch, and the first iterations of its loop."""
    s = trace.reduce(trace.read_events(RECORDED))
    assert s.n_devices == 1 and s.window_ns == 49456630.0
    assert s.busy_ns == 41154093.0
    assert dict(s.idle_by_host) == {"request.readback": 4456630.0,
                                    "request.dispatch": 2192675.0,
                                    "other": 1518022.0,
                                    "request.prep": 135210.0}
    assert trace.kernel(s, "%waterfill_batch") == (1.0, 1157696.0)
    assert s.device_ops[0] == ("%fusion.1047", 8039069.0)
