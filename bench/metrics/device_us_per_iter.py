"""Event loop: device busy time per loop iteration, in us.

Device busy time of the traced window (the union of the chip's op
intervals, averaged over the chips) over the loop iterations run: per
request, the largest ``n_steps`` of each device program, averaged over
the chips.  Moves ``sims_per_s``.
"""


def read(ctx):
    if ctx.summary is None or not ctx.summary.n_devices:
        return None
    iters = sum(sum(p[2] for p in r["programs"]) / len(r["programs"])
                for r in ctx.requests)
    if not iters:
        return None
    return ctx.summary.busy_ns * 1e-3 / iters
