"""Survey host path: the host time of one request of the window, in ms.

The benchmark's own spans around building the request's host arrays
(``request.prep``, the runner's ``grid_arrays``), dispatching it with its
host-to-device transfer (``request.dispatch``) and reading every answer
back (``request.readback``).  The wait for the device is left out.
Moves ``sims_per_s``.
"""


def read(ctx):
    reqs = ctx.requests
    if not reqs:
        return None
    return 1e3 * sum(r["prep_s"] + r["dispatch_s"] + r["readback_s"]
                     for r in reqs) / len(reqs)
