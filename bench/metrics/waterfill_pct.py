"""Waterfill kernel: the Pallas max-min kernel's share of device busy
time in the traced window, found by its stable name among the chip's ops.
Moves ``sims_per_s``.
"""
from bench import trace

# the Pallas call's HLO instruction is named after the kernels.waterfill
# entry that makes it (``%waterfill_batch.<n>``)
KERNEL = "%waterfill_batch"


def read(ctx):
    s = ctx.summary
    if s is None or s.busy_ns <= 0:
        return None
    k = trace.kernel(s, KERNEL)
    if k is None:
        return None
    return 100.0 * k[1] / s.busy_ns
