"""The one generator of the benchmark's traffic.

A cell's inputs come from data files: its configuration (the graphs,
the clusters, the survey axes and the grid points a request carries on
each chip), the graphs it names (``graphs/<name>.json``, fixed
instances, as a survey runs one dataset) and its traffic mix
(scheduler, network model, engine and the grid points of each request).
Requests run back to back (a closed loop with one client) and cycle
through the traffic file's ``requests``, each a group of
``request_points`` x chips grid point indices: one request runs every
graph on every cluster at its points.  ``--seed`` orders the cycle and
draws the answers the check compares, so every seed asks for the same
work.
"""
from __future__ import annotations

import numpy as np

from . import graphs as graph_data

MiB = 1024.0 * 1024.0


def grid_points(config) -> list[dict]:
    """The (bandwidth x imode x MSD) points of the survey grid, in the
    survey's order; the decision delay acts only where MSD > 0."""
    dd = config["decision_delay"]
    return [dict(bandwidth=bw * MiB, imode=im, msd=float(m),
                 decision_delay=dd if m > 0 else 0.0)
            for bw in config["bandwidths_mib"]
            for im in config["imodes"]
            for m in config["msds"]]


def graphs(config):
    """The configuration's graph instances, the same for every run."""
    return [graph_data.load(name) for name in config["graphs"]]


def request_order(traffic, seed: int) -> list[int]:
    """The order in which a run walks the cycle of point groups, drawn
    from ``--seed``: every seed asks for the same work in another
    order."""
    return [int(j) for j in
            np.random.default_rng(seed).permutation(len(traffic["requests"]))]


def check_requests(config, traffic, chips: int) -> None:
    """Every group of the cycle carries the configuration's
    ``request_points`` per chip, each one of the grid's points."""
    n_points = len(grid_points(config))
    size = config["request_points"] * chips
    for idx in traffic["requests"]:
        if len(idx) != size or not all(0 <= j < n_points for j in idx):
            raise ValueError(f"request {idx}: every request carries {size} "
                             f"points, each one of the grid's {n_points}")


def request_points(traffic, order, i: int) -> list[int]:
    """Indices of the grid points request ``i`` carries: the traffic
    file's ``requests`` lists one cycle of point groups."""
    return list(traffic["requests"][order[i % len(order)]])
