"""Compile and cache: the seconds of set-up that the program's own count
does not name: ``setup_s`` less its ``trace``, ``compile`` and ``host``
seconds.  Imports, the start of the chip's backend, the benchmark's own
graph loading and the warm-up request's run.  ``None`` when the program
keeps no such count.  Moves ``setup_s``.
"""
from bench import phases


def read(ctx):
    split = phases.setup_split(ctx)
    return None if split is None else split["other"]
