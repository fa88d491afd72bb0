"""The survey engine's chip benchmark (``BENCHMARK.json``, ``PERF.md``).

Everything that decides a measurement lives here, apart from the program
under test: the graph instances, the plain reference, the trace
reduction, the peaks and the work counts.  A cell is found by its name:
``configs/<config>.json``, ``traffic/<traffic>.json`` and, for each
per-layer metric, ``metrics/<metric>.py``.
"""
