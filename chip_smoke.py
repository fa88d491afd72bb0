#!/usr/bin/env python3
"""Chip smoke test: the paper-scale survey slice on a TPU.

Run from the repository root on a machine with a TPU::

    python chip_smoke.py             # one chip: survey, kernel, parity
    python chip_smoke.py --chips 4   # sharded engine vs vmap, four chips

One chip drives ``benchmarks.survey.survey()`` — the survey's own entry
point — over the paper's widest cluster group (``32x4`` and ``32x16``,
both W=32) x both shape buckets of 3 graphs per family x the full
grid's 24 (bandwidth, imode, msd) points x {blevel, greedy} x {maxmin,
simple}: 8 compile groups.  It checks the one-compile-per-group gate,
that every result is finite, that the max-min programs carry the Pallas
waterfill kernel (``tpu_custom_call``), and that the tie-free parity
graphs of ``tests/test_vectorized_dynamic.py`` agree with the reference
event loop running the deterministic scheduler twins at the suites'
tolerances.  ``--chips 4`` instead runs the same 8 groups, at 5 of the
24 points, through the sharded engine over a 4-chip mesh and the
single-chip vmap engine, and fails unless every ``SimResult`` field
is bitwise equal (DESIGN.md §9).

The persistent compile cache is on: ``$JAX_COMPILATION_CACHE_DIR`` when
set, else ``.jax_cache`` in the checkout.  Timings printed here are smoke
timings of one run, not benchmark results.  The last line of standard
output is ``{"ok": true, "device": {...}}``; without a TPU the script
exits non-zero before doing any work.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "results", "chip_smoke")
CLUSTERS = ("32x4", "32x16")
SCHEDULERS = ("blevel", "greedy")
NETMODELS = ("maxmin", "simple")
MAKESPAN_REL = 2e-3                      # the parity suites' tolerances
TRANSFER_REL, TRANSFER_ABS = 1e-3, 1.0
# --chips 4 runs points[::5] of the 24: every bandwidth, imode and MSD
# value appears, and the two engines' 16 programs stay a short run
SHARDED_POINT_STRIDE = 5


def log(msg):
    print(msg, flush=True)


def smoke_grid():
    from benchmarks.survey import FULL_GRID

    return dict(FULL_GRID, graphs_per_family=3, clusters=CLUSTERS,
                schedulers=SCHEDULERS, netmodels=NETMODELS)


def grid_groups(grid):
    """The survey's (bucket, W) layout: ``(points, encoded, buckets, W,
    cluster names, cores[K, W])`` — one cluster group by construction."""
    from benchmarks.survey import cluster_groups, grid_points
    from repro.core.graphs import encode_graph_batch, survey_names

    encoded, buckets = encode_graph_batch(
        survey_names(grid["graphs_per_family"]), seed=0, bucket=True)
    (wb, cnames, cores2d), = cluster_groups(grid["clusters"])
    return grid_points(grid), encoded, buckets, wb, cnames, cores2d


def check_finite(rows):
    bad = [r for r in rows
           if not (math.isfinite(r["time"]) and r["time"] > 0
                   and math.isfinite(r["total_transfer"])
                   and r["total_transfer"] >= 0)]
    if bad:
        raise AssertionError(f"{len(bad)} survey rows are not finite "
                             f"positive makespans, e.g. {bad[0]}")


def survey_phase(grid, cache_root):
    """The survey, cold: the compile gate, every row ``ok`` (the runner
    raises otherwise) and finite."""
    from benchmarks.survey import check_compiles, survey

    t0 = time.perf_counter()
    rows, _, stats = survey(grid, out_dir=OUT_DIR, agreement=False,
                            cache_dir=cache_root)
    wall = time.perf_counter() - t0
    check_compiles(stats)
    n_graphs = sum(len(b.split(":")[1].split(",")) for b in stats["buckets"])
    want = (len(grid["clusters"]) * n_graphs * 24
            * len(grid["schedulers"]) * len(grid["netmodels"]))
    if len(rows) != want:
        raise AssertionError(f"{len(rows)} survey rows, expected {want}")
    check_finite(rows)
    log(f"survey: {len(rows)} rows ok, {stats['compiles']} compiles for "
        f"{stats['bucket_groups']} groups ({'; '.join(stats['buckets'])}; "
        f"{'; '.join(stats['cluster_groups'])})")
    log(f"survey: cold wall {wall:.1f} s (smoke timing, compile included)")
    for group, secs in stats["group_walls"].items():
        log(f"survey: group {group} first call {secs:.1f} s (smoke timing)")
    log(f"survey: cache_hits={stats['cache_hits']} "
        f"cache_misses={stats['cache_misses']} "
        f"exec_save_errors={stats['exec_save_errors']}")
    return rows


def kernel_phase(grid, cache_root):
    """One max-min group's program, loaded from the cache the survey
    filled: it must carry the Pallas kernel, and one warm repeat of it
    is timed."""
    import numpy as np

    from repro.core.vectorized import make_grid_runner

    points, encoded, buckets, wb, _, cores2d = grid_groups(grid)
    grp = buckets[0]
    runner = make_grid_runner([encoded[n] for n in grp.names], "blevel",
                              wb, cores2d, netmodel="maxmin",
                              shape=grp.shape, batch=grp.batch,
                              cache_dir=cache_root)
    args = (runner.bspec, *runner.grid_arrays(points), runner.clusters)
    compiled = runner._fn.lower(*args).compile()
    t0 = time.perf_counter()
    ok = np.asarray(compiled(*args).ok)
    warm = time.perf_counter() - t0
    if not ok.all():
        raise AssertionError(f"warm repeat: {int((~ok).sum())} simulations "
                             f"not ok")
    log(f"kernel: warm repeat of {grp.label}/blevel/maxmin "
        f"({ok.size} sims) {warm:.3f} s (smoke timing)")
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("the maxmin grid program has no "
                             "tpu_custom_call: the Pallas waterfill did "
                             "not compile into it")
    log(f"kernel: tpu_custom_call present in the {grp.label}/blevel/maxmin "
        f"grid program")


def parity_phase():
    """Tie-free parity graphs through ``make_grid_runner`` on the chip vs
    the reference simulator running the deterministic twins."""
    import test_vectorized_dynamic as tvd

    from benchmarks.common import REF_TWIN
    from repro.core.vectorized import make_grid_runner

    points = tvd.full_grid()
    by_cluster = {}
    for make, W, cores in tvd.GRAPHS.values():
        by_cluster.setdefault((W, cores), []).append(make())
    for sched in SCHEDULERS:
        for netmodel in NETMODELS:
            worst_ms = worst_xf = 0.0
            for (W, cores), graphs in by_cluster.items():
                ms, xf = make_grid_runner([(g, None) for g in graphs],
                                          sched, W, cores,
                                          netmodel=netmodel)(points)
                for b, g in enumerate(graphs):
                    refs = tvd.reference_grid(g, REF_TWIN[sched], W, cores,
                                              points, netmodel)
                    for n, rep in enumerate(refs):
                        m, x = float(ms[b, n]), float(xf[b, n])
                        err_ms = abs(m - rep.makespan) / rep.makespan
                        err_xf = abs(x - rep.transferred_bytes)
                        label = f"{g.name}/{sched}/{netmodel}/{points[n]}"
                        if err_ms > MAKESPAN_REL:
                            raise AssertionError(
                                f"parity {label}: makespan {m} vs "
                                f"reference {rep.makespan}")
                        if err_xf > max(TRANSFER_ABS, TRANSFER_REL
                                        * abs(rep.transferred_bytes)):
                            raise AssertionError(
                                f"parity {label}: transferred {x} vs "
                                f"reference {rep.transferred_bytes}")
                        worst_ms = max(worst_ms, err_ms)
                        worst_xf = max(worst_xf, err_xf / max(
                            1.0, abs(rep.transferred_bytes)))
            n_sims = sum(len(g) for g in by_cluster.values()) * len(points)
            log(f"parity: {sched}/{netmodel} {n_sims} sims, max rel err "
                f"makespan {worst_ms:.3e} (<= {MAKESPAN_REL}), transferred "
                f"{worst_xf:.3e} (<= {TRANSFER_REL} or {TRANSFER_ABS} B)")


def twin_ratio_phase(grid, rows):
    """Reported, not asserted (paper graphs may hold float ties): the
    makespan ratio vs the reference twin for the first graph of each
    bucket on the first cluster at the first grid point."""
    from benchmarks.common import time_reference_twin
    from repro.core import MiB, parse_cluster

    points, _, buckets, _, _, _ = grid_groups(grid)
    p0 = points[0]
    cname = grid["clusters"][0]
    cores = parse_cluster(cname)
    for grp in buckets:
        gname = grp.names[0]
        for sched in grid["schedulers"]:
            for netmodel in grid["netmodels"]:
                row, = [r for r in rows
                        if r["graph_name"] == gname
                        and r["cluster_name"] == cname
                        and r["scheduler_name"] == sched
                        and r["netmodel"] == netmodel
                        and r["bandwidth"] == p0["bandwidth"] / MiB
                        and r["imode"] == p0["imode"]
                        and r["min_sched_interval"] == p0["msd"]]
                reps, _ = time_reference_twin(gname, sched, len(cores),
                                              cores, [p0],
                                              netmodel=netmodel)
                log(f"twin ratio (reported): {grp.label} {gname} {cname} "
                    f"{sched}/{netmodel} makespan vec/ref "
                    f"{row['time'] / reps[0].makespan:.4f}")


def sharded_phase(grid, cache_root, n_devices):
    """The 8 groups through the sharded engine over ``n_devices`` chips
    and through the single-chip vmap engine, at every
    ``SHARDED_POINT_STRIDE``-th grid point.  Fails unless every
    ``SimResult`` field is bitwise equal (DESIGN.md §9), every
    simulation is ``ok`` and each chunk's sharded outputs span all
    ``n_devices``.  Groups run in threads so their tracing, compiles
    and runs overlap; each verdict is printed as its group finishes."""
    import jax
    import numpy as np

    from repro.core.vectorized import (ShardedGridRunner, cache_counter,
                                       exec_counter, make_grid_runner)

    points, encoded, buckets, wb, _, cores2d = grid_groups(grid)
    points = points[::SHARDED_POINT_STRIDE]

    def one_group(i, job):
        gi, sched, netmodel = job
        grp = buckets[gi]
        kw = dict(netmodel=netmodel, shape=grp.shape, batch=grp.batch,
                  est_cache={}, cache_dir=cache_root)     # one per thread
        entries = [encoded[n] for n in grp.names]
        t0 = time.perf_counter()
        vm = make_grid_runner(entries, sched, wb, cores2d, **kw)
        sh = make_grid_runner(entries, sched, wb, cores2d,
                              engine="sharded", devices=n_devices, **kw)
        args = vm.grid_arrays(points)
        t1 = time.perf_counter()
        # the groups' single-chip vmap runs take chips 1.. in turn, so
        # they do not all queue on chip 0 in front of the sharded runs
        chip = jax.devices()[1 + i % (n_devices - 1)]
        with jax.default_device(chip):
            ref = vm._execute(*args)
        if ref.makespan.devices() != {chip}:
            raise AssertionError(f"vmap engine ran on {ref.makespan.devices()}"
                                 f", not {chip}")
        t2 = time.perf_counter()
        outs = sh.chunk_outputs(*args)
        spread = {len(out.makespan.sharding.device_set) for out in outs}
        got = ShardedGridRunner.gather(outs, vm.B, len(points))
        t3 = time.perf_counter()
        # field -> number of differing elements
        diff = {f: int(np.sum(np.asarray(a) != np.asarray(b)))
                for f, a, b in zip(ref._fields, ref, got, strict=True)
                if not np.array_equal(np.asarray(a), np.asarray(b),
                                      equal_nan=True)}
        return (f"{grp.label}/{sched}/{netmodel} (vmap on chip {chip.id})",
                diff, spread, bool(np.asarray(ref.ok).all()),
                f"set-up {t1 - t0:.1f} s, vmap {t2 - t1:.1f} s, "
                f"sharded {t3 - t2:.1f} s")

    jobs = [(gi, s, n) for gi in range(len(buckets))
            for s in grid["schedulers"] for n in grid["netmodels"]]
    n_sims = (len(grid["schedulers"]) * len(grid["netmodels"])
              * sum(len(cores2d) * len(b.names) * len(points)
                    for b in buckets))
    log(f"sharded: {len(jobs)} groups at {len(points)} grid points, "
        f"{n_sims} sims per engine")
    t0 = time.perf_counter()
    failures = []
    with cache_counter() as cc, exec_counter() as xc, \
            concurrent.futures.ThreadPoolExecutor(len(jobs)) as pool:
        for fut in concurrent.futures.as_completed(
                [pool.submit(one_group, i, job)
                 for i, job in enumerate(jobs)]):
            label, diff, spread, ok, walls = fut.result()
            verdict = "yes" if not diff else f"no, differing elements {diff}"
            log(f"sharded: {label} bitwise={verdict} "
                f"devices={sorted(spread)} ok={ok} "
                f"at {time.perf_counter() - t0:.1f} s ({walls}; smoke "
                f"timings, threads overlap)")
            if diff or spread != {n_devices} or not ok:
                failures.append(label)
    wall = time.perf_counter() - t0
    log(f"sharded: {len(jobs)} groups x 2 engines in {wall:.1f} s "
        f"(smoke timing, compile included); cache_hits={cc.hits} "
        f"cache_misses={cc.misses} exec_hits={xc.hits} "
        f"exec_misses={xc.misses} exec_save_errors={xc.save_errors}")
    if failures:
        raise AssertionError(f"sharded engine != vmap engine (or not on "
                             f"{n_devices} devices) for {failures}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-vs-vmap comparison over a "
                         "4-chip mesh")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform!r}); "
              f"this test runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT,
                    os.path.join(ROOT, "tests")]
    from repro.core.vectorized import enable_compile_cache

    cache_root = enable_compile_cache()
    log(f"device_kind: {devices[0].device_kind} ({len(devices)} visible)")
    log(f"compile cache: {cache_root}")
    grid = smoke_grid()
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(grid, cache_root, 4)
    else:
        rows = survey_phase(grid, cache_root)
        kernel_phase(grid, cache_root)
        parity_phase()
        twin_ratio_phase(grid, rows)
    log(f"total wall {time.perf_counter() - t0:.1f} s (smoke timing)")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
