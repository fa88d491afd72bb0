"""Event loop: the share of the traced window's device self time in ops
that no phase of the program's ``SIM_PHASES`` reaches (loop control,
state set-up and results, copies the compiler adds): how blind the
phase split is.

``phases.py`` charges each op of the chip to a phase; the five
``phase_*_pct`` shares add to 100.  ``None`` when the program declares
no phases.  Moves ``sims_per_s``.
"""
from bench import phases


def read(ctx):
    shares = phases.phase_shares(ctx)
    return None if shares is None else shares.get(phases.UNSCOPED)
