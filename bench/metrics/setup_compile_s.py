"""Compile and cache: the seconds of set-up spent in
XLA compiles, compile-cache fetches and executable-store loads and
saves.

The program's own count (``setup_seconds()``, phase ``compile``), taken
once set-up is over.  ``None`` when the program keeps no such count.
Moves ``setup_s``.
"""
from bench import phases


def read(ctx):
    split = phases.setup_split(ctx)
    return None if split is None else split["compile"]
