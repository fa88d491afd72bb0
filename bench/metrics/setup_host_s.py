"""Compile and cache: the seconds of set-up spent in
the runner's own host work: encoding, padding and stacking the graphs,
building the simulator and the imode estimates.

The program's own count (``setup_seconds()``, phase ``host``), taken
once set-up is over.  ``None`` when the program keeps no such count.
Moves ``setup_s``.
"""
from bench import phases


def read(ctx):
    split = phases.setup_split(ctx)
    return None if split is None else split["host"]
