"""Machine-readable perf record for the sharded engine PR (``BENCH_PR8.json``).

ISSUE 8's acceptance: the sharded survey engine at 8 forced host
devices must deliver **>= 3x grid throughput** over the cold
single-device vmap baseline on the mini grid, with every sharded row
bit-identical to the vmap path and a warm-start row showing **zero
fresh XLA compiles** out of a populated persistent cache.  Four
sections:

* **scaling** — warm grid points/sec of ``ShardedGridRunner`` at
  ``devices`` in {1, 2, 4, 8} vs the vmap baseline, bitwise parity per
  row.  ``cpu_count`` is recorded because forced *host* devices are
  slices of the same silicon: on a 1-core container the warm-compute
  ratios hover near 1.0 by construction, and the honest multi-device
  win is the next section's.
* **streaming** — ``stream_rows`` double-buffered chunking vs the
  single-shot dispatch: same bits, bounded resident bytes.
* **workers** — three fresh worker *processes* answering the same
  mini-survey request (every (scheduler, netmodel) compile group of the
  slice — the survey's one-compile-per-group contract): a cold vmap
  worker (no cache), a cold sharded worker that populates both warm
  tiers (persistent XLA cache + executable store), and a warm sharded
  worker that must serve the whole request with **zero fresh traces
  and zero fresh compiles** (``jit_traces == 0``, ``fresh_compiles ==
  0``, ``exec_hits == groups``).  The headline ``grid_throughput_x``
  is warm-sharded rows/sec over cold-vmap rows/sec — the service-level
  quantity a survey fleet sees, where trace + XLA compile time
  dominates the cold path.
* **compile_time** — the measured warm-vs-cold compile-time row
  backing the same numbers.

Output: ``BENCH_PR8.json`` at the repo root (override with ``--json``)
plus a copy under ``--out`` for the CI artifact.  A chip belongs to one
process at a time, so the parent touches no JAX backend until its
worker processes are done; it then runs the scaling and streaming
sections itself over the devices it sees — on the CPU platform 8
forced host devices (``XLA_FLAGS``, set before the backend starts and
inert on an accelerator).  CLI::

    PYTHONPATH=src python -m benchmarks.bench_pr8 --min-scaling 3.0
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

# jax and repro.core.vectorized are imported inside the functions:
# importing the simulator starts the JAX backend, and the parent must
# not hold the chip while its worker processes need it
from repro.core import MiB

DEFAULT_JSON = "BENCH_PR8.json"
FORCE_DEVICES = 8

SLICES = {
    # one shape bucket each; scaling/streaming measure the first
    # (scheduler, netmodel) group, the worker section serves them all
    "mini": dict(graphs=["fork1", "merge_neighbours"],
                 schedulers=["blevel", "random", "etf", "greedy"],
                 netmodels=["maxmin", "simple"], n_workers=4, cores=2),
    "survey": dict(graphs=None,          # survey_names(1), resolved late
                   schedulers=["blevel", "random", "etf", "greedy"],
                   netmodels=["maxmin", "simple"], n_workers=8, cores=4),
}

POINTS = [dict(imode=im, bandwidth=bw * MiB, msd=0.0,
               decision_delay=0.0, seed=3)
          for im in ("exact", "user") for bw in (32, 100)]


def _force_host_devices():
    """8 forced host devices for the in-process sections.  XLA reads the
    flag when the backend starts, so this must run before anything
    touches JAX; it only affects the CPU platform."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{FORCE_DEVICES}").strip()


def _graphs(slice_name):
    graphs = SLICES[slice_name]["graphs"]
    if graphs is None:
        from repro.core.graphs import survey_names
        graphs = list(survey_names(1))
    return graphs


def _entries(slice_name):
    from repro.core.graphs import make_graph

    sl = SLICES[slice_name]
    entries = [(make_graph(n, seed=0), None) for n in _graphs(slice_name)]
    return entries, sl["schedulers"][0], sl["n_workers"], sl["cores"]


def _full(runner, points):
    """Un-sliced SimResult[K, B, N] with the host-side prep included —
    the per-call work a survey pays."""
    from repro.core.vectorized.sim import _points_arrays

    pts, M, DD, BW, SD = _points_arrays(points)
    D = np.stack([runner._estimates(p["imode"])[0] for p in pts], axis=1)
    S = np.stack([runner._estimates(p["imode"])[1] for p in pts], axis=1)
    return runner._execute(D, S, M, DD, BW, SD)


def _timed(runner, reps):
    res = _full(runner, POINTS)                  # compile + sanity
    if not np.asarray(res.ok).all():
        raise RuntimeError(f"bench run did not finish (ok=False) on "
                           f"{runner.names}")
    t0 = time.perf_counter()
    for _ in range(reps):
        res = _full(runner, POINTS)
    wall = (time.perf_counter() - t0) / reps
    return res, wall


def _assert_bitwise(ref, res, label):
    for field, a, b in zip(ref._fields, ref, res, strict=True):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise RuntimeError(f"sharded path diverged from vmap on "
                               f"{label}: field {field}")


def bench_scaling(slice_name, reps):
    import jax
    from repro.core.vectorized import (BucketedGridRunner,
                                       ShardedGridRunner, trace_counter)

    entries, sched, W, cores = _entries(slice_name)
    vm = BucketedGridRunner(entries, sched, W, cores)
    ref, wall_v = _timed(vm, reps)
    G = ref.makespan[0].size                     # B*N grid points, K=1
    rows = {"vmap": {"devices": 1, "wall_s": round(wall_v, 4),
                     "grid_points_per_s": round(G / wall_v, 1)}}
    for D in (d for d in (1, 2, 4, 8) if d <= len(jax.devices())):
        with trace_counter() as tc:
            r = ShardedGridRunner(entries, sched, W, cores, devices=D)
            res, wall = _timed(r, reps)
        _assert_bitwise(ref, res, f"scaling/dev{D}")
        rows[f"dev{D}"] = {
            "devices": D, "wall_s": round(wall, 4),
            "grid_points_per_s": round(G / wall, 1),
            "jit_traces": tc.count, "bitwise_vs_vmap": True,
            "throughput_vs_dev1": 1.0 if D == 1 else round(
                rows["dev1"]["wall_s"] / wall, 3)}
    return rows


def bench_streaming(slice_name, reps):
    from repro.core.vectorized import ShardedGridRunner, trace_counter

    entries, sched, W, cores = _entries(slice_name)
    single = ShardedGridRunner(entries, sched, W, cores)
    ref, wall_1 = _timed(single, reps)
    with trace_counter() as tc:
        chunked = ShardedGridRunner(entries, sched, W, cores,
                                    stream_rows=8)
        res, wall_c = _timed(chunked, reps)
    _assert_bitwise(ref, res, "streaming/stream_rows=8")
    G = ref.makespan[0].size
    chunk, gp = chunked._row_chunks(G)
    return {"stream_rows": 8, "chunk_rows": chunk, "n_chunks": gp // chunk,
            "single_wall_s": round(wall_1, 4),
            "chunked_wall_s": round(wall_c, 4),
            "jit_traces": tc.count, "bitwise_vs_single": True}


_WORKER_CODE = """
import json, os, sys, time
cfg = json.loads(sys.argv[1])
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%d"
                           % cfg["force_devices"])
import numpy as np
t0 = time.perf_counter()
from repro.core import MiB
from repro.core.graphs import make_graph
from repro.core.vectorized import (make_grid_runner, trace_counter,
                                   cache_counter, exec_counter)
POINTS = [dict(imode=im, bandwidth=bw * MiB, msd=0.0,
               decision_delay=0.0, seed=3)
          for im in ("exact", "user") for bw in (32, 100)]
entries = [(make_graph(n, seed=0), None) for n in cfg["graphs"]]
makespans, rows = [], 0
with trace_counter() as tc, cache_counter() as cc, exec_counter() as xc:
    for sched in cfg["schedulers"]:
        for nm in cfg["netmodels"]:
            runner = make_grid_runner(entries, sched, cfg["n_workers"],
                                      cfg["cores"], netmodel=nm,
                                      engine=cfg["engine"],
                                      devices=cfg.get("devices"),
                                      cache_dir=cfg.get("cache_dir"))
            ms, xf = runner(POINTS)
            rows += int(np.asarray(ms).size)
            makespans += np.asarray(ms, np.float64).ravel().tolist()
wall = time.perf_counter() - t0
print(json.dumps({"wall_s": wall, "jit_traces": tc.count,
                  "cache_hits": cc.hits, "cache_misses": cc.misses,
                  "exec_hits": xc.hits, "exec_misses": xc.misses,
                  "rows": rows, "makespans": makespans}))
"""


def _run_worker(cfg):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(
                   os.path.dirname(os.path.abspath(__file__))), "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _WORKER_CODE,
                          json.dumps(cfg)],
                         env=env, capture_output=True, text=True,
                         timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"worker failed: {out.stderr[-2000:]}")
    return json.loads(out.stdout.splitlines()[-1])


def bench_workers(slice_name, cache_dir=None):
    """Fresh-process service measurements: the time a survey worker
    takes from exec to the full request's results — every (scheduler,
    netmodel) compile group of the slice — cold vs persistently-cached
    warm.  The cache (default ``<checkout>/.jax_cache/bench_pr8``) is
    emptied first and lives outside the artifact directory — only its
    hit/miss counts are part of the record."""
    from repro.core.vectorized.engine import CACHE_ENV, DEFAULT_CACHE_DIR

    if os.environ.get(CACHE_ENV):
        raise RuntimeError(f"the worker section empties its own compile "
                           f"cache to measure a cold worker; unset "
                           f"{CACHE_ENV} to run it")
    sl = SLICES[slice_name]
    n_groups = len(sl["schedulers"]) * len(sl["netmodels"])
    if cache_dir is None:
        cache_dir = os.path.join(DEFAULT_CACHE_DIR, "bench_pr8")
    shutil.rmtree(cache_dir, ignore_errors=True)
    base = {"graphs": _graphs(slice_name), "schedulers": sl["schedulers"],
            "netmodels": sl["netmodels"], "n_workers": sl["n_workers"],
            "cores": sl["cores"], "force_devices": FORCE_DEVICES}
    rows = {}
    rows["cold_vmap"] = _run_worker(
        {**base, "engine": "vmap", "force_devices": 1})
    rows["cold_sharded"] = _run_worker(
        {**base, "engine": "sharded", "cache_dir": cache_dir})
    rows["warm_sharded"] = _run_worker(
        {**base, "engine": "sharded", "cache_dir": cache_dir})
    for key, row in rows.items():
        row["grid_points_per_s"] = round(row["rows"] / row["wall_s"], 2)
        row["fresh_compiles"] = row["cache_misses"]
        row["wall_s"] = round(row["wall_s"], 2)
    for key in ("cold_vmap", "cold_sharded"):
        if rows[key]["jit_traces"] != n_groups:
            raise RuntimeError(
                f"{key} worker traced {rows[key]['jit_traces']} times "
                f"for {n_groups} (scheduler, netmodel) groups")
    if rows["warm_sharded"]["makespans"] != rows["cold_vmap"]["makespans"]:
        raise RuntimeError("warm sharded worker diverged from cold vmap")
    if rows["cold_sharded"]["cache_misses"] < n_groups:
        raise RuntimeError("cold sharded worker compiled fewer programs "
                           "than groups — cache accounting broken")
    warm = rows["warm_sharded"]
    if (warm["fresh_compiles"] != 0 or warm["jit_traces"] != 0
            or warm["exec_hits"] != n_groups):
        raise RuntimeError(
            f"warm worker not warm: {warm['fresh_compiles']} fresh "
            f"compiles, {warm['jit_traces']} traces, "
            f"{warm['exec_hits']}/{n_groups} executable-store loads")
    for row in rows.values():
        del row["makespans"]                     # parity checked; bulky
    return {**rows,
            "n_groups": n_groups,
            "bitwise_warm_vs_cold_vmap": True,
            "grid_throughput_x": round(
                warm["grid_points_per_s"]
                / rows["cold_vmap"]["grid_points_per_s"], 2)}


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="results",
                    help="artifact output directory (default 'results')")
    ap.add_argument("--json", default=DEFAULT_JSON,
                    help=f"perf-record path (default {DEFAULT_JSON!r})")
    ap.add_argument("--reps", type=int, default=3,
                    help="warm repetitions per measurement")
    ap.add_argument("--slice", default="mini", choices=sorted(SLICES),
                    help="bench slice (default 'mini')")
    ap.add_argument("--min-scaling", type=float, default=None,
                    help="fail unless workers.grid_throughput_x reaches "
                         "this factor (the ISSUE-8 gate is 3.0)")
    args = ap.parse_args(argv)
    _force_host_devices()
    t0 = time.time()
    workers = bench_workers(args.slice)      # before this process holds JAX
    import jax

    record = {"generated_by": "benchmarks.bench_pr8",
              "backend": jax.default_backend(),
              "device_kind": jax.devices()[0].device_kind,
              "slice": args.slice,
              "n_devices": len(jax.devices()),
              "cpu_count": os.cpu_count(),
              "grid_points": len(_graphs(args.slice)) * len(POINTS)}
    record["scaling"] = bench_scaling(args.slice, args.reps)
    record["streaming"] = bench_streaming(args.slice, args.reps)
    os.makedirs(args.out, exist_ok=True)
    record["workers"] = w = workers
    record["compile_time"] = {
        "cold_sharded_wall_s": w["cold_sharded"]["wall_s"],
        "warm_sharded_wall_s": w["warm_sharded"]["wall_s"],
        "warm_speedup_x": round(w["cold_sharded"]["wall_s"]
                                / w["warm_sharded"]["wall_s"], 2)}
    record["wall_s"] = round(time.time() - t0, 1)
    for key, row in record["scaling"].items():
        print(f"bench_pr8/scaling_{key},{row['wall_s']},"
              f"{row['grid_points_per_s']}")
    for key in ("cold_vmap", "cold_sharded", "warm_sharded"):
        row = w[key]
        print(f"bench_pr8/worker_{key},{row['wall_s']},"
              f"{row['grid_points_per_s']},traces={row['jit_traces']},"
              f"misses={row['cache_misses']},hits={row['cache_hits']},"
              f"exec_hits={row['exec_hits']}")
    print(f"bench_pr8/grid_throughput_x,0,{w['grid_throughput_x']}")
    for path in (args.json, os.path.join(args.out,
                                         os.path.basename(args.json))):
        with open(path, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
            f.write("\n")
    print(f"# bench_pr8: wrote {args.json} "
          f"(+ copy under {args.out}/) in {record['wall_s']}s")
    if args.min_scaling is not None:
        got = w["grid_throughput_x"]
        if got < args.min_scaling:
            print(f"error: warm-sharded vs cold-vmap grid throughput "
                  f"{got} < {args.min_scaling}", file=sys.stderr)
            sys.exit(1)
        print(f"# scaling gate passed ({got} >= {args.min_scaling})")


if __name__ == "__main__":
    main()
