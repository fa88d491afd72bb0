"""Paper-grid survey runner (DESIGN.md §5).

The paper's headline claim — neglected details (network model, scheduler
internals, MSD, imodes) shift results by up to an order of magnitude —
is demonstrated by a survey over the full (graph family x cluster x
bandwidth x netmodel x scheduler x imode x msd) grid.  This runner
sweeps that grid through the batched vectorized simulator: graphs are
padded into shape buckets (``vectorized.specs.pad_specs``), clusters
are padded into worker-count buckets (``w_bucket``: next power of two,
shorter clusters gain inert zero-core workers), and the grid is grouped
by **(bucket, padded W, scheduler, netmodel)** — one
``BucketedGridRunner`` jit compilation per group executes the whole
[clusters x graphs x bandwidth x imode x msd] sub-grid as a single
device call, with the per-worker ``cores`` vector a *traced argument*
riding its own vmap axis.  The measured jit-trace count must equal the
group count (``--assert-compiles``; CI's bench-smoke regression gate
against silent per-graph or per-cluster recompiles).

Clusters are named by the shared grammar ``repro.core.parse_cluster``:
homogeneous ``8x4`` or heterogeneous ``1x8+4x2`` (one 8-core worker plus
four 2-core workers — padded to W=8, it shares the ``8x4`` group's one
compiled program).

It emits an estee-schema CSV::

    graph_name, cluster_name, bandwidth, netmodel, scheduler_name,
    imode, min_sched_interval, time, total_transfer

into ``results/survey.csv`` (``bandwidth`` in MiB/s, ``time`` =
makespan seconds, ``total_transfer`` in bytes, ``min_sched_interval`` =
MSD seconds), plus honest agreement/speedup rows vs the reference
event loop running each scheduler's deterministic twin
(``results/survey_agreement.csv``, now with per-group ``bucket`` /
``group_size`` / ``compile_count`` columns and a ``__pergraph_path__``
row comparing one bucket compilation against the PR-2 one-runner-per-
graph path).

The graph axis is a **dataset** (``--dataset``, DESIGN.md §6):
``default`` keeps the per-family survey representatives under the
tuned ``specs.T_EDGES`` bucket edges (so the mini grid's compile-count
contract stays byte-stable), while any named ``repro.workloads``
manifest — e.g. ``wfcommons-mini``, 3 recipe families x 2 scales —
sweeps that manifest's instances under bucket edges *derived from the
dataset itself* (``workloads.compute_bucket_edges``), closing the
ROADMAP "adaptive bucket edges" item.

CLI::

    PYTHONPATH=src python -m benchmarks.survey --mini   # CI bench-smoke
    PYTHONPATH=src python -m benchmarks.survey --full   # paper grid
    PYTHONPATH=src python -m benchmarks.survey --mini \
        --dataset wfcommons-mini --assert-compiles     # recipe smoke
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

from repro.core import MiB, parse_cluster
from repro.core.graphs import encode_graph_batch, survey_names
from repro.core.vectorized import (DynamicGridRunner, cache_counter,
                                   compile_cache_root, exec_counter,
                                   make_grid_runner, setup_timer,
                                   trace_counter)
from repro.workloads import w_bucket

from .common import geomean, time_reference_twin, write_csv

SCHEMA = ("graph_name", "cluster_name", "bandwidth", "netmodel",
          "scheduler_name", "imode", "min_sched_interval", "time",
          "total_transfer", "dataset")

AGREE_SCHEMA = ("graph_name", "scheduler_name", "cluster_name", "netmodel",
                "bucket", "group_size", "compile_count", "makespan_ratio",
                "vec_us_per_sim", "ref_us_per_sim", "speedup",
                "bucket_cold_s", "pergraph_cold_s", "total_compiles",
                "bucket_groups", "dataset")

OUT_DIR = os.environ.get("SURVEY_OUT", "results")

# CI-sized: 1 graph per family (all four representatives — incl. the
# recipes family's montage-77-s0 — share the T160 shape bucket, so
# every (cluster, scheduler, netmodel) combination is exactly one
# compilation), 2 clusters incl. one heterogeneous
MINI_GRID = dict(
    dataset="default",
    graphs_per_family=1,
    clusters=("8x4", "1x8+4x2"),
    bandwidths_mib=(32, 256),
    netmodels=("maxmin", "simple"),
    schedulers=("blevel", "random", "etf", "greedy"),
    imodes=("exact", "user"),
    msds=(0.0, 0.1),
)

FULL_GRID = dict(
    dataset="default",
    graphs_per_family=3,
    clusters=("8x4", "16x4", "32x4", "1x8+4x2"),
    bandwidths_mib=(32, 128, 512, 2048),
    netmodels=("maxmin", "simple"),
    schedulers=("blevel", "tlevel", "mcp", "random", "etf", "greedy"),
    imodes=("exact", "user", "mean"),
    msds=(0.0, 0.1),
)


def grid_points(grid):
    """The (bandwidth x imode x msd) batch every runner executes in one
    vmap call.  Static schedulers ignore msd beyond the initial
    invocation; greedy is genuinely rate-limited by it."""
    return [dict(bandwidth=bw * MiB, imode=im, msd=m,
                 decision_delay=0.05 if m > 0 else 0.0)
            for bw in grid["bandwidths_mib"]
            for im in grid["imodes"]
            for m in grid["msds"]]


def dataset_axis(grid):
    """The grid's graph axis: ``(dataset_name, graph_items, t_edges)``.
    The ``default`` dataset is the classic per-family representative
    slice under the tuned ``specs.T_EDGES`` (``t_edges=None``); named
    manifests are built *once*, their bucket edges derived from the
    built graphs (DESIGN.md §6), and the prebuilt ``(name, graph)``
    pairs handed to ``encode_graph_batch`` so nothing is generated or
    parsed twice."""
    ds = grid.get("dataset", "default")
    if ds == "default":
        return ds, survey_names(grid["graphs_per_family"]), None
    from repro.workloads import (build_dataset, compute_bucket_edges,
                                 get_manifest)

    man = get_manifest(ds)
    graphs = build_dataset(man)
    return ds, list(graphs.items()), compute_bucket_edges(
        graphs, k=man.bucket_k)


def cluster_groups(cluster_names):
    """Group cluster name strings by padded worker count: returns
    ``[(W, [name, ...], cores i32[K, W]), ...]`` ordered by W, each
    entry one traced-cores vmap axis for the runners."""
    by_w = {}
    for cname in cluster_names:
        cores = parse_cluster(cname)
        by_w.setdefault(w_bucket(len(cores)), []).append(cname)
    out = []
    for wb in sorted(by_w):
        names = by_w[wb]
        cores2d = np.stack([
            np.pad(np.asarray(parse_cluster(n), np.int32),
                   (0, wb - len(parse_cluster(n))))
            for n in names])
        out.append((wb, names, cores2d))
    return out


def estee_rows(gname, cname, netmodel, scheduler, points, ms, xfer,
               dataset="default"):
    """Map one graph's batched results onto the estee CSV schema."""
    rows = []
    for p, m, x in zip(points, ms, xfer, strict=True):
        rows.append({
            "graph_name": gname,
            "cluster_name": cname,
            "bandwidth": p["bandwidth"] / MiB,
            "netmodel": netmodel,
            "scheduler_name": scheduler,
            "imode": p["imode"],
            "min_sched_interval": p["msd"],
            "time": float(m),
            "total_transfer": float(x),
            "dataset": dataset,
        })
    return rows


def agreement_pass(grid, points, encoded, groups, runners, stats):
    """Agreement/speedup rows for the first (cluster group, netmodel):
    per (graph, first cluster) the bucketed makespan vs the reference
    twin on the *unpadded* cluster, per group the warm batched per-sim
    time, and one ``__pergraph_path__`` row timing the whole first
    bucket against PR-2-style per-graph runners (compile + run each —
    the cost the bucketing removes).  The sentinel row also persists the
    sweep-wide ``total_compiles``/``bucket_groups`` so the cross-PR
    trend view can track compile regressions."""
    netmodel = grid["netmodels"][0]
    agree_rows = []
    for sched in grid["schedulers"]:
        for gi, grp in enumerate(groups):
            runner, _, cnames = runners[(sched, netmodel, gi)]
            cname = cnames[0]
            cores = parse_cluster(cname)
            t0 = time.perf_counter()
            ms2, _ = runner(points)              # warm, steady state
            n_sims = len(cnames) * runner.B * len(points)
            vec_us = (time.perf_counter() - t0) / n_sims * 1e6
            for b, gname in enumerate(grp.names):
                reps, ref_us = time_reference_twin(
                    gname, sched, len(cores), cores, points[:1],
                    netmodel=netmodel)
                agree_rows.append({
                    "graph_name": gname, "scheduler_name": sched,
                    "cluster_name": cname, "netmodel": netmodel,
                    "bucket": grp.label, "group_size": runner.B,
                    "compile_count": 1,
                    "makespan_ratio": float(ms2[0, b, 0]) / reps[0].makespan,
                    "vec_us_per_sim": vec_us,
                    "ref_us_per_sim": ref_us,
                    "speedup": ref_us / vec_us,
                    "dataset": stats["dataset"],
                })
    # the compile-amortisation row: B per-graph runners (each pays its
    # own jit trace) vs the one bucketed compilation recorded cold
    sched = grid["schedulers"][0]
    grp = groups[0]
    runner, bucket_cold, cnames = runners[(sched, netmodel, 0)]
    cores = parse_cluster(cnames[0])
    t0 = time.perf_counter()
    for gname in grp.names:
        g, spec = encoded[gname]
        DynamicGridRunner(g, sched, len(cores), cores, netmodel=netmodel,
                          spec=spec)(points)
    pergraph_cold = time.perf_counter() - t0
    agree_rows.append({
        "graph_name": "__pergraph_path__", "scheduler_name": sched,
        "cluster_name": cnames[0], "netmodel": netmodel,
        "bucket": grp.label, "group_size": runner.B,
        "compile_count": runner.B,
        "bucket_cold_s": round(bucket_cold, 3),
        "pergraph_cold_s": round(pergraph_cold, 3),
        "speedup": pergraph_cold / bucket_cold,
        "total_compiles": stats["compiles"],
        "bucket_groups": stats["bucket_groups"],
        "dataset": stats["dataset"],
    })
    return agree_rows


def _make_diagnose(runners, grid):
    """A lazy closure over the first retained runner that re-traces its
    un-vmapped simulator for graph 0 vs graph 1 (and cluster row 0 vs
    row 1) and structurally diffs the jaxprs — ``repro.analysis
    .diff_traces``.  Called only when ``check_compiles`` is about to
    fail, so the AssertionError can *name* the first divergent equation
    (or blame the Python side when the traces are identical)."""
    key = (grid["schedulers"][0], grid["netmodels"][0], 0)
    if key not in runners:
        return None
    runner, _, _ = runners[key]

    def diagnose():
        import jax
        import jax.numpy as jnp

        from repro.analysis import diff_traces

        take = lambda b: jax.tree_util.tree_map(  # noqa: E731
            lambda x: x[b], runner.bspec)
        D, S = runner._estimates("exact")

        def args(b, k):
            return (take(b), jnp.asarray(D[b]), jnp.asarray(S[b]),
                    jnp.float32(0.0), jnp.float32(0.0),
                    jnp.float32(32 * MiB), jnp.int32(0),
                    jnp.asarray(runner.clusters[k]))

        parts = []
        if runner.B > 1:
            parts.append("graph axis (bucket member 0 vs 1):\n"
                         + diff_traces(runner.run, args(0, 0), args(1, 0),
                                       labels=(runner.names[0],
                                               runner.names[1])))
        if runner.clusters.shape[0] > 1:
            parts.append("cluster axis (row 0 vs 1):\n"
                         + diff_traces(runner.run, args(0, 0), args(0, 1),
                                       labels=("cluster0", "cluster1")))
        return "\n".join(parts) if parts else \
            "single-graph, single-cluster group: nothing to diff"

    return diagnose


def survey(grid, out_dir=OUT_DIR, agreement=True, engine="vmap",
           devices=None, stream_rows=None, cache_dir=None):
    """Run the whole grid; returns (rows, agreement_rows, stats) and
    writes ``survey.csv`` / ``survey_agreement.csv`` under ``out_dir``.
    ``stats`` carries the measured jit compile count vs the expected
    one-per-(bucket, cluster, scheduler, netmodel) group count —
    engine-invariant: the sharded engine's shard_map sits under one jit
    per group, so ``--assert-compiles`` holds at any device count, and
    persistent-cache hits (``cache_dir``) are counted separately
    (``cache_hits``/``cache_misses``) so cached XLA loads are never
    mistaken for fresh traces.  With a populated executable store
    (``<cache root>/exec``, sharded engine) a group may skip tracing
    altogether — those loads are counted as ``exec_hits`` and the gate
    checks ``traces + exec_hits == groups``; executables the store
    failed to persist are ``exec_save_errors``.  ``stats["setup_s"]``
    splits the sweep's set-up seconds into tracing, compiling and the
    runners' host work (``setup_timer``); ``stats["group_walls"]``
    holds each group's first-call seconds (compile or cache load, plus
    the run), ``stats["backlog_peaks"]`` the most (object, destination)
    keys that waited outside a simulation's flow frontier in each group
    (``SimResult.backlog_peak``), and ``stats["device"]`` names what the
    grid ran on."""
    points = grid_points(grid)
    dataset, names, t_edges = dataset_axis(grid)
    encoded, groups = encode_graph_batch(names, seed=0, bucket=True,
                                         t_edges=t_edges)
    wgroups = cluster_groups(grid["clusters"])
    rows = []
    runners = {}                 # only the agreement slice is retained
    group_walls = {}             # first call (compile + run) per group
    backlog_peaks = {}           # most flow keys waiting, per group
    est_caches = [{} for _ in groups]    # shared per bucket, not per runner
    with trace_counter() as tc, cache_counter() as cc, \
            exec_counter() as xc, setup_timer() as st:   # no cross-sweep bleed
        for wb, cnames, cores2d in wgroups:
            for sched in grid["schedulers"]:
                for netmodel in grid["netmodels"]:
                    for gi, grp in enumerate(groups):
                        runner = make_grid_runner(
                            [encoded[n] for n in grp.names], sched,
                            wb, cores2d, netmodel=netmodel,
                            shape=grp.shape, batch=grp.batch,
                            est_cache=est_caches[gi], engine=engine,
                            devices=devices, stream_rows=stream_rows,
                            cache_dir=cache_dir)
                        t0 = time.perf_counter()
                        ms, xfer = runner(points)  # compile+run [K, B, N]
                        cold_s = time.perf_counter() - t0
                        label = f"{grp.label}/W{wb}/{sched}/{netmodel}"
                        group_walls[label] = cold_s
                        backlog_peaks[label] = runner.backlog_peak
                        if (wb == wgroups[0][0]
                                and netmodel == grid["netmodels"][0]):
                            runners[(sched, netmodel, gi)] = (runner, cold_s,
                                                              cnames)
                        for k, cname in enumerate(cnames):
                            for b, gname in enumerate(grp.names):
                                rows.extend(estee_rows(
                                    gname, cname, netmodel, sched, points,
                                    ms[k, b], xfer[k, b], dataset=dataset))
    stats = dict(
        compiles=tc.count,
        bucket_groups=(len(wgroups) * len(grid["schedulers"])
                       * len(grid["netmodels"]) * len(groups)),
        buckets=[f"{grp.label}:{','.join(grp.names)}" for grp in groups],
        cluster_groups=[f"W{wb}:{','.join(cn)}" for wb, cn, _ in wgroups],
        dataset=dataset,
        t_edges=("T_EDGES" if t_edges is None else tuple(t_edges)),
        engine=engine,
        cache_hits=cc.hits,
        cache_misses=cc.misses,
        exec_hits=xc.hits,
        exec_misses=xc.misses,
        exec_save_errors=xc.save_errors,
        setup_s=st.seconds,
        group_walls=group_walls,
        backlog_peaks=backlog_peaks,
        device=device_info(),
    )
    stats["diagnose"] = _make_diagnose(runners, grid)
    agree_rows = (agreement_pass(grid, points, encoded, groups, runners,
                                 stats)
                  if agreement else [])
    write_csv("survey", rows, out_dir=out_dir, fieldnames=list(SCHEMA))
    write_csv("survey_agreement", agree_rows, out_dir=out_dir,
              fieldnames=list(AGREE_SCHEMA))
    return rows, agree_rows, stats


def device_info():
    """The device the grid ran on, as JAX reports it."""
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def report(rows, agree_rows, stats):
    """Print the benchmark-driver ``name,us_per_call,derived`` rows."""
    dev = stats["device"]
    print(f"# device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}")
    for a in agree_rows:
        if a["graph_name"] == "__pergraph_path__":
            print(f"survey/bucket_vs_pergraph_cold,"
                  f"{a['bucket_cold_s'] * 1e6:.0f},{a['speedup']:.2f}")
            continue
        print(f"survey/agree_{a['graph_name']}/{a['scheduler_name']},"
              f"{a['ref_us_per_sim']:.0f},{a['makespan_ratio']:.4f}")
        print(f"survey/speedup_{a['graph_name']}/{a['scheduler_name']},"
              f"{a['vec_us_per_sim']:.0f},{a['speedup']:.1f}")
    plain = [a for a in agree_rows if a["graph_name"] != "__pergraph_path__"]
    if plain:
        print(f"survey/speedup_geomean,0,"
              f"{geomean([a['speedup'] for a in plain]):.2f}")
    print(f"survey/jit_compiles,0,{stats['compiles']}")
    print(f"survey/cache_hits,0,{stats.get('cache_hits', 0)}")
    print(f"survey/cache_misses,0,{stats.get('cache_misses', 0)}")
    print(f"survey/exec_hits,0,{stats.get('exec_hits', 0)}")
    print(f"survey/exec_save_errors,0,{stats.get('exec_save_errors', 0)}")
    for phase, secs in stats.get("setup_s", {}).items():
        print(f"survey/setup_{phase}_s,0,{secs:.3f}")
    for label, peak in stats.get("backlog_peaks", {}).items():
        print(f"survey/backlog_peak/{label},0,{peak}")
    print(f"survey/bucket_groups,0,{stats['bucket_groups']}")
    print(f"survey/cluster_groups,0,{len(stats['cluster_groups'])}")
    print(f"survey/rows,0,{len(rows)}")
    print(f"# dataset {stats['dataset']}: t_edges={stats['t_edges']}")


def check_compiles(stats):
    """The one-compilation-per-(bucket, W, scheduler, netmodel)-group
    contract (ISSUE 3/4 acceptance; asserted by CI so a per-graph or
    per-cluster recompile regression fails the build).  A group served
    from a populated executable store never traces, so the gate counts
    ``compiles + exec_hits`` — still exactly one program per group."""
    fresh = stats["compiles"] + stats.get("exec_hits", 0)
    if fresh != stats["bucket_groups"]:
        msg = (
            f"jit compile count {stats['compiles']} + executable-store "
            f"loads {stats.get('exec_hits', 0)} != bucket-group count "
            f"{stats['bucket_groups']} — the bucketed survey is "
            f"recompiling per graph or per cluster (buckets: "
            f"{stats['buckets']}; clusters: "
            f"{stats.get('cluster_groups', [])})")
        diagnose = stats.get("diagnose")
        if diagnose is not None:
            try:
                msg += "\nrecompile diagnosis (repro.analysis):\n" \
                       + diagnose()
            except Exception as e:  # diagnosis must never mask the gate
                msg += f"\n(recompile diagnosis itself failed: {e!r})"
        raise AssertionError(msg)


def run(fast=True):
    """Entry point for ``benchmarks.run`` (--only survey)."""
    rows, agree_rows, stats = survey(MINI_GRID if fast else FULL_GRID)
    report(rows, agree_rows, stats)
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--mini", action="store_true",
                      help="CI-sized grid (default)")
    mode.add_argument("--full", action="store_true",
                      help="paper-scale grid (slow)")
    ap.add_argument("--dataset", default="default",
                    help="graph-axis dataset: 'default' (per-family "
                         "survey representatives, tuned T_EDGES) or a "
                         "repro.workloads manifest name (e.g. "
                         "'wfcommons-mini') with bucket edges derived "
                         "from the dataset")
    ap.add_argument("--out", default=OUT_DIR,
                    help=f"output directory (default {OUT_DIR!r})")
    ap.add_argument("--no-agreement", action="store_true",
                    help="skip the reference-loop agreement/speedup pass")
    ap.add_argument("--assert-compiles", action="store_true",
                    help="fail unless the jit compile count equals the "
                         "bucket-group count (CI regression gate)")
    ap.add_argument("--engine", choices=("vmap", "sharded"), default="vmap",
                    help="grid executor: single-device vmap (default) or "
                         "the shard_map engine over a 1-D device mesh "
                         "(DESIGN.md §9; force host devices via XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--devices", type=int, default=None,
                    help="sharded engine: number of mesh devices "
                         "(default: all visible)")
    ap.add_argument("--stream-rows", type=int, default=None,
                    help="sharded engine: double-buffered chunk size in "
                         "grid rows (default: whole grid in one batch)")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent compilation cache directory (warm "
                         "worker restarts skip all XLA compiles; default "
                         "$JAX_COMPILATION_CACHE_DIR, else .jax_cache in "
                         "the checkout; the variable wins when set)")
    args = ap.parse_args()
    grid = dict(FULL_GRID if args.full else MINI_GRID,
                dataset=args.dataset)
    t0 = time.time()
    rows, agree_rows, stats = survey(grid, out_dir=args.out,
                                     agreement=not args.no_agreement,
                                     engine=args.engine, devices=args.devices,
                                     stream_rows=args.stream_rows,
                                     cache_dir=compile_cache_root(
                                         args.cache_dir))
    report(rows, agree_rows, stats)
    print(f"# survey[{stats['dataset']}/{stats['engine']}]: {len(rows)} "
          f"grid points, {stats['compiles']} jit "
          f"compiles for {stats['bucket_groups']} (bucket, W, scheduler, "
          f"netmodel) groups ({'; '.join(stats['buckets'])}; "
          f"{'; '.join(stats['cluster_groups'])}) in {time.time() - t0:.1f}s "
          f"-> {os.path.join(args.out, 'survey.csv')}")
    if args.assert_compiles:
        try:
            check_compiles(stats)
        except AssertionError as e:
            print(f"error: {e}", file=sys.stderr)
            sys.exit(1)
        print("# compile-count assertion passed")


if __name__ == "__main__":
    main()
