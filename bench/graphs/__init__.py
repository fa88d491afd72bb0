"""The task graphs of the benchmark's traffic, one data file each.

``<name>.json`` holds one instance of an ESTEE survey graph (elementary,
irw and pegasus families), made by the program's generators at the
survey's instance seed 0: per task ``[duration s, user estimate s, cpus,
category, input object ids]`` in id order, per object ``[producing task
id, size B, user estimate B]`` in id order.  Kept as data, so that the
inputs a cell measures cannot move with the program.
"""
from __future__ import annotations

import json
import os

from ..reference.taskgraph import TaskGraph

GRAPH_DIR = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> TaskGraph:
    with open(os.path.join(GRAPH_DIR, name + ".json")) as f:
        data = json.load(f)
    g = TaskGraph(data["name"])
    for duration, expected, cpus, category, _ in data["tasks"]:
        g.new_task(duration, cpus=cpus, expected_duration=expected,
                   name=category)
    for parent, size, expected in data["objects"]:
        g.new_object(g.tasks[parent], size).expected_size = expected
    for t, (*_, inputs) in zip(g.tasks, data["tasks"], strict=True):
        g.add_dependencies(t, [g.objects[i] for i in inputs])
    g.validate()
    return g
