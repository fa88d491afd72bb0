"""Grid mesh: how evenly a request's loop iterations fall on the chips.

Per request, the mean over the chips of the iterations their shard ran
(its largest ``n_steps``) over the most any chip ran, averaged over the
requests of the window.  A request waits for its slowest shard.  Only a
request that ran on more than one chip has something to read.  Moves
``sims_per_s``.
"""


def read(ctx):
    shares = []
    for r in ctx.requests:
        most = [p[2] for p in r["programs"]]
        if len(most) > 1 and max(most):
            shares.append(sum(most) / len(most) / max(most))
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
