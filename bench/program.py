"""The system under test, as the benchmark drives it.

Every contact with the program (the ``repro`` package under ``src/``) is
in this module: the graphs the benchmark generated are handed over as the
program's own ``TaskGraph``, the runner is the survey's per-group entry
(``make_grid_runner`` -> ``BucketedGridRunner`` / ``ShardedGridRunner``),
and a request goes through the runner's own steps: host arrays
(``grid_arrays``), dispatch, and readback of every ``SimResult`` field.
"""
from __future__ import annotations

import os
import sys
import typing

import numpy as np


class Result(typing.NamedTuple):
    """One request's answers, ``[K clusters, B graphs, N points]`` each,
    and per device program ``(lanes, sum of loop steps, max loop
    steps)``: a vmapped ``while_loop`` runs until its slowest lane."""
    makespan: np.ndarray
    transferred: np.ndarray
    ok: np.ndarray
    n_steps: np.ndarray
    programs: list


def import_program(root: str):
    """Put the checkout's ``src/`` on the path and import the program;
    raises ``ImportError`` when the checkout has no program."""
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.core.vectorized  # noqa: F401

    return sys.modules["repro"]


def to_program_graph(graph):
    """The program's ``TaskGraph`` with the same tasks, objects, arcs and
    estimates, in the same id order."""
    from repro.core.taskgraph import TaskGraph

    out = TaskGraph(graph.name)
    for t in graph.tasks:
        nt = out.new_task(t.duration, outputs=[o.size for o in t.outputs],
                          cpus=t.cpus, expected_duration=t.expected_duration,
                          name=t.name)
        for o, no in zip(t.outputs, nt.outputs, strict=True):
            no.expected_size = o.expected_size
    for t in graph.tasks:
        out.add_dependencies(out.tasks[t.id],
                             [out.objects[o.id] for o in t.inputs])
    return out


def cluster_matrix(clusters, padded_workers: int) -> np.ndarray:
    """``i32[K, W]`` per-worker cores of each cluster name, padded with
    zero-core workers to ``padded_workers``."""
    rows = []
    for name in clusters:
        cores = []
        for part in name.split("+"):
            n, c = part.split("x")
            cores.extend([int(c)] * int(n))
        if len(cores) > padded_workers:
            raise ValueError(f"cluster {name} has {len(cores)} workers, more "
                             f"than the configuration's {padded_workers}")
        rows.append(cores + [0] * (padded_workers - len(cores)))
    return np.asarray(rows, np.int32)


class Program:
    """One survey compile group of the program, built for a cell."""

    def __init__(self, config, traffic, graphs, cache_root: str,
                 chips: int = 1):
        from repro.core.vectorized import encode_graph, make_grid_runner

        T, O, E = config["shape"]
        entries = []
        for g in graphs:
            pg = to_program_graph(g)
            spec = encode_graph(pg)
            if spec.T > T or spec.O > O or spec.E > E:
                raise ValueError(
                    f"graph {g.name} (T={spec.T}, O={spec.O}, E={spec.E}) "
                    f"does not fit the configuration's shape {T, O, E}")
            entries.append((pg, spec))
        self.engine = traffic["engine"]
        self.runner = make_grid_runner(
            entries, traffic["scheduler"], config["padded_workers"],
            cluster_matrix(config["clusters"], config["padded_workers"]),
            netmodel=traffic["netmodel"], shape=(T, O, E),
            cache_dir=cache_root, engine=self.engine,
            devices=chips if self.engine == "sharded" else None)

    def prep(self, points):
        return self.runner.grid_arrays(points)

    def dispatch(self, arrays):
        if self.engine == "sharded":
            return self.runner.chunk_outputs(*arrays)
        return self.runner._execute(*arrays)

    @staticmethod
    def wait(out):
        import jax

        jax.block_until_ready(out)

    def readback(self, out, n_points: int) -> Result:
        outs = out if isinstance(out, list) else [out]
        programs = []
        for o in outs:
            for shard in o.n_steps.addressable_shards:
                steps = np.asarray(shard.data)
                programs.append((int(steps.size), int(steps.sum()),
                                 int(steps.max())))
        res = self.gather(outs, n_points)
        return Result(np.asarray(res.makespan), np.asarray(res.transferred),
                      np.asarray(res.ok), np.asarray(res.n_steps), programs)

    def gather(self, outs, n_points: int):
        """The host ``SimResult[K, B, N]`` of the device outputs."""
        import jax

        if self.engine == "sharded":
            return type(self.runner).gather(outs, self.runner.B, n_points)
        out, = outs
        return jax.tree_util.tree_map(np.asarray, out)

    @staticmethod
    def counters():
        """The program's own odometers: simulator traces, persistent
        compile-cache hits and misses, executable-store loads."""
        from repro.core.vectorized import (cache_counter, exec_counter,
                                           trace_counter)

        return trace_counter(), cache_counter(), exec_counter()
