"""Compile and cache: the seconds of set-up spent in
tracing the program to a jaxpr and lowering it to MLIR, as JAX's own
compile events report it.

The program's own count (``setup_seconds()``, phase ``trace``), taken
once set-up is over.  ``None`` when the program keeps no such count.
Moves ``setup_s``.
"""
from bench import phases


def read(ctx):
    split = phases.setup_split(ctx)
    return None if split is None else split["trace"]
