"""The plain reference of a survey simulation.

The event-driven ESTEE simulator (paper §4) with the deterministic twins
of the in-loop schedulers, every tie broken by the smallest index, that
of downloads of equal priority too (the lowest consuming arc, where the
program's own reference takes the order it meets them in).  The
modules are copies of the program's ``core/taskgraph.py``,
``core/netmodels.py``, ``core/worker.py``, ``core/imodes.py``,
``core/simulator.py`` and ``core/schedulers/{base,det}.py``, kept here so
that no change to the program can move what its results are compared
with.  Nothing here imports the program.
"""
from __future__ import annotations

from .schedulers.det import (DetBlevelScheduler, DetETFScheduler,
                             DetMCPScheduler, DetRandomScheduler,
                             DetTlevelScheduler, GreedyWorkerScheduler)
from .simulator import Simulator, parse_cluster, resolve_workers

# the program's in-loop scheduler name -> its deterministic twin
TWINS = {"blevel": DetBlevelScheduler, "tlevel": DetTlevelScheduler,
         "mcp": DetMCPScheduler, "etf": DetETFScheduler,
         "random": DetRandomScheduler, "greedy": GreedyWorkerScheduler}


def simulate(graph, cluster: str, scheduler: str, netmodel: str, point,
             bandwidth=None):
    """``(makespan s, transferred bytes)`` of one survey simulation: the
    graph on the unpadded cluster ``cluster`` (``"32x4"``) at one grid
    point (``bandwidth`` B/s, ``imode``, ``msd``, ``decision_delay``;
    ``bandwidth`` overrides the point's)."""
    rep = Simulator(
        graph, resolve_workers(parse_cluster(cluster)),
        TWINS[scheduler](seed=point.get("seed", 0)), netmodel=netmodel,
        bandwidth=point["bandwidth"] if bandwidth is None else bandwidth,
        imode=point["imode"], msd=point["msd"],
        decision_delay=point["decision_delay"]).run()
    return rep.makespan, rep.transferred_bytes
