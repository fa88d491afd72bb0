"""Waterfill kernel: its share of the roofline.

The least time the chip could take for the kernel's calls in the traced
window (``work.waterfill_work``: flow endpoints, active flags, capacities
and rates read or written once, one pass over the flow -> resource
incidence, for every simulation lane of the call; at the chip's published
peaks, ``peaks.py``) over the kernel's device time.  Each call solves all
the lanes that the chip's program holds.  Moves ``sims_per_s``.
"""
from bench import peaks, trace, work
from bench.metrics.waterfill_pct import KERNEL


def read(ctx):
    s = ctx.summary
    if s is None:
        return None
    k = trace.kernel(s, KERNEL)
    if k is None or k[1] <= 0:
        return None
    calls, ns = k
    lanes = ctx.requests[0]["programs"][0][0]
    flops, nbytes = work.waterfill_work(lanes, ctx.config["padded_workers"],
                                        ctx.config["download_slots"])
    least, _ = work.least_time_s(flops, nbytes, peaks.peaks(ctx.device_kind))
    return 100.0 * calls * least / (ns * 1e-9)
