"""A cell, found by its name.

``BENCHMARK.json`` at the root of the checkout names the cell's
configuration and traffic mix; their files are ``configs/<config>.json``
(the path the configuration's entry gives) and ``traffic/<traffic>.json``,
and each per-layer metric is read by ``metrics/<metric>.py``, whose
``read(ctx)`` returns a number or ``None`` when the run holds nothing to
read.  A new cell or metric is new files and a new entry, never an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    root: str

    @classmethod
    def load(cls, name: str, root: str = ROOT) -> "Cell":
        bench = load_benchmark(root)
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                           f"{sorted(by_name)})")
        w = by_name[name]
        entry, = [c for c in bench["configs"] if c["name"] == w["config"]]
        with open(os.path.join(root, entry["file"])) as f:
            config = json.load(f)
        with open(os.path.join(root, "bench", "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)

        def here(m):
            return name in m.get("workloads", [name])
        e2e = [m for m in bench["end_to_end"] if here(m)]
        names = {m["name"] for m in e2e}
        per_layer = [m for m in bench["per_layer"]
                     if here(m) and m["moves"] in names]
        return cls(name, int(w["chips"]), config, traffic, e2e, per_layer,
                   root)

    def reader(self, metric: str):
        """The ``read`` function of ``metrics/<metric>.py``."""
        path = os.path.join(self.root, "bench", "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
