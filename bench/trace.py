"""From a profiler trace to device busy time, idle gaps and kernel time.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
flattens it into plain ``Event`` tuples; everything after that works on
those tuples alone, so a small recorded chip trace (``data/``) tests the
reduction.  Device ops are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, nested as the program nests them (a
``while`` op holds the ops of its body); host spans are the events of
``/host:CPU``.
All times are in nanoseconds on the trace's own clock.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import typing

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
# host spans the idle gaps are attributed to, as the harness names them
HOST_SPANS = ("request.prep", "request.dispatch", "request.wait",
              "request.readback")


class Event(typing.NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_events(trace_dir: str) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir`` that
    lies on a device ops line or on the host plane."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            # a device op's name is its HLO instruction: keep the name
            # before " = ", which the program's code gives it
            out.extend(Event(plane.name, line.name,
                             ev.name.split(" = ", 1)[0] if device
                             else ev.name,
                             float(ev.start_ns), float(ev.duration_ns))
                       for ev in line.events)
    return out


def save_events(events, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([list(e) for e in events], f)


def read_events(path: str) -> list[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*e) for e in json.load(f)]


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


class Summary(typing.NamedTuple):
    """One traced window, reduced.  ``busy_ns`` is an average over the
    devices that ran an op; an op's self time leaves out the ops nested
    in it."""
    window_ns: float
    n_devices: int
    busy_ns: float
    device_ops: list          # [(op name, self ns summed over devices)]
    idle_by_host: list        # [(host span or "other", idle ns)] by time
    ops: dict                 # op name -> (calls, ns, self ns), summed


def window_of(events) -> tuple[float, float]:
    """The harness's ``bench.window`` span, else the device ops' extent."""
    spans = [e for e in events if e.plane == HOST_PLANE
             and e.name == WINDOW_SPAN]
    if spans:
        return spans[-1].start_ns, spans[-1].end_ns
    dev = [e for e in events if e.plane.startswith(DEVICE_PLANE_PREFIX)]
    if not dev:
        raise ValueError("the trace holds no window span and no device op")
    return min(e.start_ns for e in dev), max(e.end_ns for e in dev)


def check_complete(events, lo, hi, slack=0.05):
    """Raise when the devices' ops stop while the host still waits for
    them: the profiler holds a bounded number of events (some six million
    on a v5e) and drops the rest, which would read as idle time.  Only
    the device that ends last counts: on a mesh the others may finish
    their shards early and idle while the host waits for the slowest."""
    waits = [e for e in events if e.plane == HOST_PLANE
             and e.name == "request.wait"]
    dev = [e for e in events if e.plane.startswith(DEVICE_PLANE_PREFIX)]
    if not dev:
        return
    end = max(e.end_ns for e in dev)
    for w in waits:
        if w.start_ns < end < w.end_ns \
                and w.end_ns - end > slack * (hi - lo):
            raise ValueError(
                f"the devices' ops stop {(w.end_ns - end) * 1e-9:.3f} s "
                f"before the host's wait for them ends: the trace "
                f"dropped events; trace a shorter window")


def reduce(events, top: int = 10) -> Summary:
    lo, hi = window_of(events)
    check_complete(events, lo, hi)
    by_plane = {}
    for e in events:
        if e.plane.startswith(DEVICE_PLANE_PREFIX) and e.end_ns > lo \
                and e.start_ns < hi:
            by_plane.setdefault(e.plane, []).append(e)
    busy_per, ops = [], {}
    for evs in by_plane.values():
        busy = union(clip([(e.start_ns, e.end_ns) for e in evs], lo, hi))
        busy_per.append(busy)
        for e, own in self_times(evs):
            calls, ns, self_ns = ops.get(e.name, (0, 0.0, 0.0))
            ops[e.name] = (calls + 1, ns + e.dur_ns, self_ns + own)
    n_dev = len(busy_per)
    busy_ns = sum(total(b) for b in busy_per) / n_dev if n_dev else 0.0
    spans = [e for e in events if e.plane == HOST_PLANE
             and e.name in HOST_SPANS]
    spans.sort(key=lambda e: e.start_ns)
    idle = {}
    for busy in busy_per:
        for gap in _gaps(busy, lo, hi):
            for who, ns in _host_activity(gap, spans):
                idle[who] = idle.get(who, 0.0) + ns / n_dev
    return Summary(
        window_ns=hi - lo, n_devices=n_dev, busy_ns=busy_ns,
        device_ops=sorted(((n, own) for n, (_, _, own) in ops.items()),
                          key=lambda x: -x[1])[:top],
        idle_by_host=sorted(idle.items(), key=lambda x: -x[1])[:top],
        ops=ops)


def self_times(evs):
    """``(event, self ns)`` of each event of one device's ops line: its
    duration less that of the events directly nested in it."""
    order = sorted(evs, key=lambda e: (e.start_ns, -e.dur_ns))
    child = [0.0] * len(order)
    stack = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack:
            child[stack[-1]] += e.dur_ns
        stack.append(i)
    return [(e, e.dur_ns - c) for e, c in zip(order, child, strict=True)]


def _gaps(busy, lo, hi):
    t = lo
    for s, e in busy:
        if s > t:
            yield (t, s)
        t = max(t, e)
    if hi > t:
        yield (t, hi)


def _host_activity(gap, spans):
    """``(host span, ns)`` of an idle gap split over the host spans that
    overlap it (sorted by start), the rest as ``other``."""
    left = gap[1] - gap[0]
    i = bisect.bisect_left([sp.start_ns for sp in spans], gap[1])
    for sp in spans[:i]:
        ov = min(gap[1], sp.end_ns) - max(gap[0], sp.start_ns)
        if ov > 0:
            left -= ov
            yield sp.name, ov
    if left > 0:
        yield "other", left


def kernel(summary: Summary, name: str) -> tuple[int, float] | None:
    """``(calls, ns)`` per device of the device ops whose name contains
    ``name``, or ``None`` when the trace holds none."""
    hits = [(c, ns) for op, (c, ns, _) in summary.ops.items() if name in op]
    if not hits or not summary.n_devices:
        return None
    return (sum(c for c, _ in hits) / summary.n_devices,
            sum(ns for _, ns in hits) / summary.n_devices)
