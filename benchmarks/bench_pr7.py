"""Machine-readable perf record for the event-frontier PR (``BENCH_PR7.json``).

ISSUE 7's acceptance: with the ready frontier on (the default), the
static max-min simulator must deliver **>= 2x events/sec on the T2048
bucket at ``16x4``** vs the ``frontier=False`` escape hatch (the PR-4
slot-pool baseline), with agreement recorded.  This runner measures,
per bench graph from ``bench_pr4.BENCH_GRAPHS``:

* **static** — events/sec of the static max-min simulator, frontier on
  vs off (flow slots on in both; the frontier is the only delta).
* **dynamic** — the same toggle for the dynamic blevel simulator.

Agreement per row: makespans must match bit-exactly; ``transferred``
must match to 1e-5 relative (the frontier+slot mode accumulates bytes
per event instead of summing a per-edge array at the end, so the f32
summation order differs — DESIGN.md §3).  ``n_events``/``n_steps``
are recorded for both modes: the step counts are identical by design
(the baseline loop already advances past every same-timestamp batch),
so the win this file demonstrates is per-step cost, not step count.

Output: ``BENCH_PR7.json`` at the repo root (override with ``--json``)
plus a copy under ``--out`` (default ``results/``) for the bench-smoke
artifact.  CLI::

    PYTHONPATH=src python -m benchmarks.bench_pr7 --min-speedup 2.0
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np
import jax

from repro.core import MiB, parse_cluster
from repro.core.imodes import encode_imode
from repro.core.vectorized import (build, encode_graph,
                                   make_bucket_simulator,
                                   make_bucket_dynamic_simulator)
from repro.core.vectorized.specs import (frontier_caps_for, pad_spec,
                                         pad_to, round_up, t_bucket)

from .bench_pr4 import BENCH_GRAPHS

DEFAULT_JSON = "BENCH_PR7.json"
XFER_RTOL = 1e-5        # f32 summation-order tolerance on transferred


def _time_run(run, args, reps):
    res = run(*args)
    jax.block_until_ready(res)               # compile + sanity
    t0 = time.perf_counter()
    for _ in range(reps):
        res = run(*args)
        jax.block_until_ready(res)
    wall = (time.perf_counter() - t0) / reps
    if not bool(np.asarray(res.ok)):
        raise RuntimeError("bench run did not finish (ok=False)")
    return res, wall


def _row_agreement(row, label):
    if row["frontier_makespan"] != row["baseline_makespan"]:
        raise RuntimeError(
            f"frontier path diverged from baseline on {label}: makespan "
            f"{row['frontier_makespan']} != {row['baseline_makespan']}")
    base = row["baseline_transferred"]
    dev = abs(row["frontier_transferred"] - base) / max(1.0, abs(base))
    if dev > XFER_RTOL:
        raise RuntimeError(
            f"transferred diverged on {label}: relative dev {dev:.2e} "
            f"> {XFER_RTOL}")
    row["makespan_exact"] = True
    row["transferred_rel_dev"] = round(dev, 9)
    row["events_per_s_speedup"] = round(
        row["frontier_events_per_s"] / row["baseline_events_per_s"], 2)


def bench_static(reps=5):
    """Static max-min events/sec, frontier on vs off, per bench graph
    padded to its shape bucket.  Returns ``{bucket_label: row}``."""
    out = {}
    for make, cname in BENCH_GRAPHS:
        g = make()
        spec = encode_graph(g)
        shape = (t_bucket(spec.T), round_up(spec.O), round_up(spec.E))
        bspec = pad_spec(spec, shape)
        label = f"T{shape[0]}xO{shape[1]}xE{shape[2]}"
        cores = parse_cluster(cname)
        W = len(cores)
        bw = np.float32(100 * MiB)
        d, s = encode_imode(g, "exact")
        aw, prio = jax.jit(build(spec, n_workers=W, cores=cores,
                                 scheduler="blevel"))(d, s, bw)
        aw_p = pad_to(np.asarray(aw), shape[0], 0).astype(np.int32)
        prio_p = pad_to(np.asarray(prio), shape[0], 0.0).astype(np.float32)
        cf, ct = frontier_caps_for(shape, n_workers=W)
        row = {"graph": g.name, "cluster": cname, "edges": int(spec.E),
               "frontier_caps": {"CF": cf, "CT": ct}}
        for key, flag in (("baseline", False), ("frontier", True)):
            run = jax.jit(make_bucket_simulator(
                W, cores, "maxmin", frontier=flag))
            res, wall = _time_run(
                run, (bspec, aw_p, prio_p, None, None, bw), reps)
            row[f"{key}_makespan"] = float(np.asarray(res.makespan))
            row[f"{key}_transferred"] = float(np.asarray(res.transferred))
            row[f"{key}_events"] = int(np.asarray(res.n_events))
            row[f"{key}_steps"] = int(np.asarray(res.n_steps))
            row[f"{key}_events_per_s"] = round(
                int(np.asarray(res.n_events)) / wall, 1)
        _row_agreement(row, f"static/{label}")
        out[label] = row
    return out


def bench_dynamic(reps=3):
    """Dynamic blevel/max-min events/sec, frontier on vs off."""
    out = {}
    for make, cname in BENCH_GRAPHS:
        g = make()
        spec = encode_graph(g)
        shape = (t_bucket(spec.T), round_up(spec.O), round_up(spec.E))
        bspec = pad_spec(spec, shape)
        label = f"T{shape[0]}xO{shape[1]}xE{shape[2]}"
        cores = parse_cluster(cname)
        W = len(cores)
        bw = np.float32(100 * MiB)
        d, s = encode_imode(g, "exact")
        d_p = pad_to(np.asarray(d, np.float32), shape[0], 0.0)
        s_p = pad_to(np.asarray(s, np.float32), shape[1], 0.0)
        row = {"graph": g.name, "cluster": cname, "edges": int(spec.E)}
        for key, flag in (("baseline", False), ("frontier", True)):
            run = jax.jit(make_bucket_dynamic_simulator(
                W, cores, "blevel", "maxmin", frontier=flag))
            res, wall = _time_run(
                run, (bspec, d_p, s_p, np.float32(0), np.float32(0), bw,
                      np.int32(0), None), reps)
            row[f"{key}_makespan"] = float(np.asarray(res.makespan))
            row[f"{key}_transferred"] = float(np.asarray(res.transferred))
            row[f"{key}_events"] = int(np.asarray(res.n_events))
            row[f"{key}_steps"] = int(np.asarray(res.n_steps))
            row[f"{key}_events_per_s"] = round(
                int(np.asarray(res.n_events)) / wall, 1)
        _row_agreement(row, f"dynamic/{label}")
        out[label] = row
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="results",
                    help="artifact output directory (default 'results')")
    ap.add_argument("--json", default=DEFAULT_JSON,
                    help=f"perf-record path (default {DEFAULT_JSON!r})")
    ap.add_argument("--reps", type=int, default=5,
                    help="warm repetitions per measurement")
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="fail unless the T2048 static events/sec speedup "
                         "reaches this factor (the ISSUE-7 gate is 2.0)")
    args = ap.parse_args(argv)
    record = {"generated_by": "benchmarks.bench_pr7",
              "backend": jax.default_backend(),
              "transferred_rtol": XFER_RTOL}
    t0 = time.time()
    record["static"] = bench_static(reps=args.reps)
    record["dynamic"] = bench_dynamic(reps=max(1, args.reps // 2))
    for section in ("static", "dynamic"):
        for label, row in record[section].items():
            print(f"bench_pr7/{section}_events_per_s_{label},"
                  f"{1e6 / row['frontier_events_per_s']:.0f},"
                  f"{row['events_per_s_speedup']}")
    record["wall_s"] = round(time.time() - t0, 1)
    os.makedirs(args.out, exist_ok=True)
    for path in (args.json, os.path.join(args.out,
                                         os.path.basename(args.json))):
        with open(path, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
            f.write("\n")
    print(f"# bench_pr7: wrote {args.json} "
          f"(+ copy under {args.out}/) in {record['wall_s']}s")
    if args.min_speedup is not None:
        t2048 = [r for label, r in record["static"].items()
                 if label.startswith("T2048")]
        if not t2048:
            print("error: no T2048 static row to gate on", file=sys.stderr)
            sys.exit(1)
        got = t2048[0]["events_per_s_speedup"]
        if got < args.min_speedup:
            print(f"error: T2048 static frontier speedup {got} < "
                  f"{args.min_speedup}", file=sys.stderr)
            sys.exit(1)
        print(f"# speedup gate passed ({got} >= {args.min_speedup})")


if __name__ == "__main__":
    main()
