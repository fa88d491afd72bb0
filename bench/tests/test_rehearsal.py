"""A rehearsal of the harness on the CPU, in the test process only.

``rehearse`` drives a cell's own files through ``run.run`` at a tiny
size (one or a few grid points per request, a subset of the graphs, one
request in the window), past the look for a chip that ``run.main`` makes.
A later PR checks a new cell's files here without chip time.  The
measurement path itself still refuses a CPU (``test_main_refuses_a_cpu``).
"""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import cells, program, run

SEED = 3000000017


def rehearse(cell, tmp_path, *, requests=((0,),), graphs=None,
             traced=False, program_cls=program.Program):
    cell.traffic["requests"] = [list(r) for r in requests]
    cell.config["request_points"] = len(requests[0]) // cell.chips
    cell.traffic["check"]["sample"] = 8
    if graphs is not None:
        cell.config["graphs"] = graphs
    program.import_program(cells.ROOT)
    return run.run(cell, SEED, 0.0, traced, time.perf_counter(),
                   program_cls=program_cls, root=str(tmp_path))


def test_cell_rehearses_correct(tmp_path, interpret_waterfill):
    out = rehearse(cells.Cell.load("t160-blevel-maxmin"), tmp_path,
                   graphs=["merge_triplets", "montage"], traced=True)
    assert out["correct"], out["compared"]
    assert out["attempted"] == 2 * 2 and out["failed"] == 0
    assert {"host_ms_per_request", "exec_ready_s",
            "lane_occupancy_pct"} <= set(out["metrics"])
    assert list(out)[-1] == "compared"


def four_chip_cell(tmp_path):
    """The four-chip cell kept for a later benchmark (``PERF.md`` §7):
    its traffic and its metric are files here, its entry is not yet in
    ``BENCHMARK.json``."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(cells.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = cells.load_benchmark()
    bench["workloads"].append({
        "name": "t160-blevel-maxmin-x4", "config": "estee-w32-t160",
        "traffic": "t160-blevel-maxmin-x4", "chips": 4, "why": "test"})
    bench["per_layer"].append({
        "name": "shard_balance_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "grid mesh",
        "moves": "sims_per_s", "workloads": ["t160-blevel-maxmin-x4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cells.Cell.load("t160-blevel-maxmin-x4", root=str(root))


def test_four_chip_cell_rehearses_correct(tmp_path):
    out = rehearse(four_chip_cell(tmp_path), tmp_path,
                   requests=[(0, 9, 16, 1)], graphs=["merge_triplets", "sipht"],
                   traced=True)
    assert out["correct"], out["compared"]
    assert "shard_balance_pct" in out["metrics"]


def test_new_cell_and_metric_are_new_files_only(tmp_path):
    """A cell and a per-layer metric added as files and entries, with no
    file of the harness edited, are picked up by name."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(cells.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = cells.load_benchmark()
    with open(root / "bench" / "configs" / "estee-w32-t160.json") as f:
        config = json.load(f)
    config.update(name="tiny-w4", graphs=["merge_triplets"],
                  clusters=["4x4"], padded_workers=4, request_points=2)
    (root / "bench" / "configs" / "tiny-w4.json").write_text(
        json.dumps(config))
    (root / "bench" / "traffic" / "tiny-greedy.json").write_text(json.dumps(
        {"scheduler": "greedy", "netmodel": "simple", "engine": "vmap",
         "requests": [[0, 9], [1, 8]],
         "check": {"sample": 4, "gap_worst_limit": 1e-4}}))
    (root / "bench" / "metrics" / "requests_run.py").write_text(
        "def read(ctx):\n    return len(ctx.requests)\n")
    bench["configs"].append({"name": "tiny-w4", "source": "test",
                             "file": "bench/configs/tiny-w4.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny", "config": "tiny-w4",
                               "traffic": "tiny-greedy", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "requests_run", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "sims_per_s",
                               "workloads": ["tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.Cell.load("tiny", root=str(root))
    out = run.run(cell, SEED, 0.0, True, time.perf_counter(),
                  root=str(tmp_path))
    assert out["correct"], out["compared"]
    assert out["metrics"]["requests_run"] == {"value": 1,
                                              "unit": "requests"}
    out = run.run(cell, SEED, 0.0, False, time.perf_counter(),
                  root=str(tmp_path))
    assert set(out["metrics"]) == {"sims_per_s", "setup_s"}


# faults planted under the timed path: each must make the run not correct

class StateUnchanged(program.Program):
    """The event loop returns its state unchanged: nothing finishes."""

    def readback(self, out, n_points):
        r = super().readback(out, n_points)
        return r._replace(makespan=np.full_like(r.makespan, np.nan),
                          transferred=np.zeros_like(r.transferred),
                          ok=np.zeros_like(r.ok),
                          n_steps=np.zeros_like(r.n_steps))


class HalfBatchLeftOut(program.Program):
    """Half of the graphs of every request are never simulated."""

    def readback(self, out, n_points):
        r = super().readback(out, n_points)
        half = r.ok.shape[1] // 2
        ms, ok = r.makespan.copy(), r.ok.copy()
        ms[:, half:], ok[:, half:] = np.nan, False
        return r._replace(makespan=ms, ok=ok)


class AnswerAltered(program.Program):
    """Every makespan is off by a thousandth where it is produced."""

    def readback(self, out, n_points):
        r = super().readback(out, n_points)
        return r._replace(makespan=r.makespan * np.float32(1.001))


class ExchangeLeftOut(program.Program):
    """The host reads only the first chip's shard and takes it for
    every chip's."""

    def gather(self, outs, n_points):
        import jax

        def first_shard(x):
            d = np.asarray(x.addressable_shards[0].data)
            return np.concatenate([d] * len(x.addressable_shards))
        return super().gather(
            [jax.tree_util.tree_map(first_shard, o) for o in outs], n_points)


@pytest.mark.parametrize("fault", [StateUnchanged, HalfBatchLeftOut,
                                   AnswerAltered])
def test_fault_is_not_correct(tmp_path, fault):
    out = rehearse(cells.Cell.load("t160-blevel-maxmin"), tmp_path,
                   requests=[(0, 9)], graphs=["merge_triplets", "montage"],
                   program_cls=fault)
    assert not out["correct"], out["compared"]


def test_exchange_left_out_is_not_correct(tmp_path):
    out = rehearse(four_chip_cell(tmp_path), tmp_path,
                   requests=[(0, 9, 16, 1)],
                   graphs=["merge_triplets", "sipht"],
                   program_cls=ExchangeLeftOut)
    assert not out["correct"], out["compared"]


def test_main_refuses_a_cpu(capsys):
    assert run.main(["--workload", "t160-blevel-maxmin", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def test_fails_without_the_program(tmp_path):
    """A checkout of only ``BENCHMARK.json`` and ``bench/`` gives no
    result and a non-zero exit."""
    shutil.copytree(os.path.join(cells.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "t160-blevel-maxmin",
         "--seed", "1", "--seconds", "1"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout == ""
    proc = subprocess.run(
        [sys.executable, "-c", "import sys; sys.path.insert(0, '.'); "
         "from bench import program; program.import_program('.')"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and "No module named 'repro'" in proc.stderr
