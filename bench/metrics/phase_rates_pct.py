"""Event loop: the share of the traced window's device self time in the
phase ``sim.rates``: the network model's flow rates: the
Pallas max-min waterfill and its glue, or the simple model's rates.

``phases.py`` charges each op of the chip to a phase of the program's
``SIM_PHASES``; the five ``phase_*_pct`` shares add to 100.  ``None``
when the program declares no phases.  Moves ``sims_per_s``.
"""
from bench import phases


def read(ctx):
    shares = phases.phase_shares(ctx)
    return None if shares is None else shares.get("sim.rates")
