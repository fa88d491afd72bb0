"""Event loop: the share of the traced window's device self time in the
phase ``sim.schedule``: applying due assignments,
the scheduler's invocations, and the static schedule made before the
loop.

``phases.py`` charges each op of the chip to a phase of the program's
``SIM_PHASES``; the five ``phase_*_pct`` shares add to 100.  ``None``
when the program declares no phases.  Moves ``sims_per_s``.
"""
from bench import phases


def read(ctx):
    shares = phases.phase_shares(ctx)
    return None if shares is None else shares.get("sim.schedule")
