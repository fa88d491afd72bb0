"""Device: the share of the traced window in which no op ran on the chip
(1 - the union of the device op intervals over the window), averaged over
the chips.  Moves ``sims_per_s``.
"""


def read(ctx):
    s = ctx.summary
    if s is None or not s.n_devices or s.window_ns <= 0:
        return None
    return 100.0 * (1.0 - s.busy_ns / s.window_ns)
