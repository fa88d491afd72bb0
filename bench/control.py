"""The control of the correctness check: the reference in the program's
place, in the precision below the configuration's.

The configurations state float32 (the program's simulators are
float32 end to end).  The control is the plain reference with every
duration, size, estimate and the bandwidth rounded to bfloat16 before it
runs, its arithmetic otherwise unchanged: a lower bound on what running
the simulation in bfloat16 would change.  Its answers stand in for the
program's over the sample a run would draw, and are judged by
``check.py`` against the reference, with the cell's limits; the control
has to come out not correct.

    python3 bench/control.py --workload <cell> --seeds <a,b,c>

prints one JSON line per seed with the numbers compared.  It needs no
chip: the control is host code.
"""
from __future__ import annotations

import argparse
import copy
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import cells, check, reference, traffic as gen  # noqa: E402


def bf16(x):
    import ml_dtypes
    import numpy as np

    return None if x is None else float(np.asarray(x, ml_dtypes.bfloat16))


def bf16_graph(graph):
    g = copy.deepcopy(graph)
    for t in g.tasks:
        t.duration = bf16(t.duration)
        t.expected_duration = bf16(t.expected_duration)
    for o in g.objects:
        o.size = bf16(o.size)
        o.expected_size = bf16(o.expected_size)
    return g


def all_keys(config):
    n_points = len(gen.grid_points(config))
    return [(k, b, p) for k in range(len(config["clusters"]))
            for b in range(len(config["graphs"])) for p in range(n_points)]


def reading(cell, seed: int) -> dict:
    """The compared numbers of the control on ``seed``: over the sample a
    run on ``seed`` whose window answered every key of the grid would
    draw."""
    config, traffic = cell.config, cell.traffic
    graphs = gen.graphs(config)
    points = gen.grid_points(config)
    keys = all_keys(config)
    low = {}

    @functools.cache
    def low_graph(b):
        return bf16_graph(graphs[b])

    def answer(k, b, p):
        if (k, b, p) not in low:
            ms, xf = reference.simulate(
                low_graph(b), config["clusters"][k], traffic["scheduler"],
                traffic["netmodel"], points[p],
                bandwidth=bf16(points[p]["bandwidth"]))
            low[(k, b, p)] = (ms, xf, True, 0)
        return low[(k, b, p)]

    # the sample draws on loop steps only for its first key; the control
    # has none, so every key ties and the draw is the same as a run's
    # over the same keys up to that first key
    stub = {key: (0.0, 0.0, True, 0) for key in keys}
    picked = check.sample(stub, traffic["check"]["sample"], seed)
    answers = {key: answer(*key) for key in picked}
    refs = check.reference_answers(picked, graphs, config, traffic, points)
    numbers = check.compare(answers, refs, 0, 0)
    correct, compared = check.verdict(numbers, check.limits(traffic))
    return {"seed": seed, "correct": correct, "compared": compared,
            "gap_worst": numbers["gap_worst"], "sampled": numbers["sampled"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = cells.Cell.load(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(reading(cell, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
