"""Event loop: the share of loop iterations that did a simulation's work.

A vmapped ``while_loop`` runs until its slowest lane is done, so each
device program runs as many iterations as its longest simulation takes
(``SimResult.n_steps``, exact per-lane counts); the share is the sum of
the lanes' steps over lanes x that maximum, over every program of the
window (one per chip and request).  Moves ``sims_per_s``.
"""


def read(ctx):
    progs = [p for r in ctx.requests for p in r["programs"]]
    den = sum(lanes * most for lanes, _, most in progs)
    if not den:
        return None
    return 100.0 * sum(steps for _, steps, _ in progs) / den
