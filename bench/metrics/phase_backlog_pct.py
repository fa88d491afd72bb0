"""Event loop: the share of the traced window's device self time in the
phase ``sim.backlog``, inside ``sim.ready``: the exact pick while wanted
downloads wait outside the flow frontier, and their refill.

``phases.py`` charges each op of the chip to the innermost phase of the
program's ``SIM_PHASES`` that its scope path names, so these ops leave
``sim.ready``'s share.  ``None`` when the program declares no such
phase.  Moves ``sims_per_s``.
"""
from bench import phases


def read(ctx):
    shares = phases.phase_shares(ctx)
    return None if shares is None else shares.get("sim.backlog")
