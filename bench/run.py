"""Run one cell of the survey engine's benchmark and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout on a machine that holds the cell's chips.
Set-up builds the cell's graphs, the survey group's runner and warms it
with one request; the window then runs identical-shape
requests back to back (one client, closed loop) for ``--seconds`` and
reads every answer back.  Once the window has closed, a sample of the
answers is compared with the plain reference (``check.py``).  The last
line of standard output is the result as JSON: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window.  Without a TPU, or with fewer chips
than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import cells, check, program, traffic as gen, trace  # noqa: E402

# fixed paths inside the checkout (gitignored): the compile cache is part
# of what a later run finds, so its directory never moves
CACHE_DIR = ".jax_cache"
TRACE_DIR = os.path.join(".bench_trace", "{workload}")
TPU_LOG_DIR = os.path.join(".bench_trace", "tpu_logs")
# a traced window is cut to this (one request at the least): the chip's
# trace holds some 70,000 ops a second, and reading them back is host
# time and memory of the run
TRACE_SECONDS = 5.0
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileWatch:
    """Counts jaxpr traces and XLA compiles in the process."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event in COMPILE_EVENTS:
            self.count += 1


def serve(prog, points, traffic, order, seconds):
    """The closed loop: requests back to back until ``seconds`` have
    passed since the first began.  Returns each request's answers, grid
    point indices and host spans."""
    from jax.profiler import TraceAnnotation

    results, points_of, records = [], [], []
    t0 = time.perf_counter()
    i = 0
    while True:
        idx = gen.request_points(traffic, order, i)
        pts = [points[j] for j in idx]
        ts = [time.perf_counter()]
        with TraceAnnotation("request.prep"):
            arrays = prog.prep(pts)
        ts.append(time.perf_counter())
        with TraceAnnotation("request.dispatch"):
            out = prog.dispatch(arrays)
        ts.append(time.perf_counter())
        with TraceAnnotation("request.wait"):
            prog.wait(out)
        ts.append(time.perf_counter())
        with TraceAnnotation("request.readback"):
            res = prog.readback(out, len(pts))
        ts.append(time.perf_counter())
        del out
        results.append(res)
        points_of.append(idx)
        records.append(dict(
            start=ts[0], end=ts[4], prep_s=ts[1] - ts[0],
            dispatch_s=ts[2] - ts[1], wait_s=ts[3] - ts[2],
            readback_s=ts[4] - ts[3], sims=int(res.ok.size),
            ok=int(res.ok.sum()), programs=res.programs))
        i += 1
        if ts[4] - t0 >= seconds:
            return results, points_of, records


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def run(cell, seed: int, seconds: float, traced: bool, t_start: float,
        program_cls=program.Program, root: str = ROOT):
    """One run of ``cell``; returns the result line as a dict."""
    import jax

    watch = CompileWatch()
    devices = jax.devices()[:cell.chips]
    config, traffic = cell.config, cell.traffic
    graphs = gen.graphs(config)
    points = gen.grid_points(config)
    gen.check_requests(config, traffic, cell.chips)
    order = gen.request_order(traffic, seed)

    t0 = time.perf_counter()
    prog = program_cls(config, traffic, graphs,
                       os.path.join(root, CACHE_DIR), chips=cell.chips)
    t_built = time.perf_counter() - t0
    prog.prep(points)                  # every imode's host estimates
    tc, cc, xc = prog.counters()
    with tc, cc, xc:
        t0 = time.perf_counter()
        first = gen.request_points(traffic, order, 0)
        warm = prog.dispatch(prog.prep([points[j] for j in first]))
        t_ready = time.perf_counter() - t0
        prog.wait(warm)
        prog.readback(warm, len(first))
        del warm
    # what set-up made lives on; later collections need not walk it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"device: platform={devices[0].platform} "
        f"device_kind={devices[0].device_kind} count={len(jax.devices())}")
    log(f"setup: {setup_s:.3f} s; runner built in {t_built:.3f} s, first "
        f"dispatch {t_ready:.3f} s; simulator traces {tc.count}, compile "
        f"cache hits {cc.hits} misses {cc.misses}, executable loads "
        f"{xc.hits} misses {xc.misses} save errors {xc.save_errors}")

    trace_dir = os.path.join(root, TRACE_DIR.format(workload=cell.name))
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    compiles0 = watch.count
    tc, cc, xc = prog.counters()
    with tc, cc, xc, jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        results, points_of, records = serve(
            prog, points, traffic, order,
            min(seconds, TRACE_SECONDS) if traced else seconds)
    if traced:
        jax.profiler.stop_trace()
    if watch.count != compiles0 or tc.count or cc.hits or cc.misses \
            or xc.hits or xc.misses:
        raise RuntimeError(
            f"the window traced or compiled: {watch.count - compiles0} "
            f"compile events, {tc.count} simulator traces, cache hits "
            f"{cc.hits} misses {cc.misses}, executable loads {xc.hits}")
    window_s = records[-1]["end"] - records[0]["start"]
    attempted = sum(r["sims"] for r in records)
    completed = sum(r["ok"] for r in records)
    for n, r in enumerate(records):
        log(f"request {n}: {r['sims']} sims, {r['ok']} ok, wall "
            f"{r['end'] - r['start']:.4f} s (prep {r['prep_s']:.4f}, "
            f"dispatch {r['dispatch_s']:.4f}, wait {r['wait_s']:.4f}, "
            f"readback {r['readback_s']:.4f}); loop steps per program "
            f"{[p[2] for p in r['programs']]}")
    mem = memory_peak(devices)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    del prog
    gc.collect()

    t0 = time.perf_counter()
    answers, inconsistent, failed = check.collect(results, points_of)
    keys = check.sample(answers, traffic["check"]["sample"], seed)
    refs = check.reference_answers(keys, graphs, config, traffic, points)
    numbers = check.compare(answers, refs, inconsistent, failed)
    correct, compared = check.verdict(numbers, check.limits(traffic))
    log(f"check: {numbers['sampled']} of {len(answers)} distinct answers "
        f"against the reference in {time.perf_counter() - t0:.3f} s; widest "
        f"gap {numbers['gap_worst']!r}")

    ctx = types.SimpleNamespace(
        requests=records, config=config, traffic=traffic, cell=cell.name,
        setup={"setup_s": setup_s, "exec_ready_s": t_built + t_ready},
        device_kind=dev.device_kind, summary=None)
    out = {"correct": correct, "attempted": attempted,
           "failed": attempted - completed, "device": device}
    if traced:
        t0 = time.perf_counter()
        summary = trace.reduce(trace.load_events(trace_dir))
        ctx.summary = summary
        device["busy_s"] = summary.busy_ns * 1e-9
        device["window_s"] = summary.window_ns * 1e-9
        out["breakdown"] = {
            "device_ops": [[n, ns * 1e-9 / summary.n_devices]
                           for n, ns in summary.device_ops],
            "idle_gaps": [[n, ns * 1e-9] for n, ns in summary.idle_by_host]}
        metrics = {}
        for m in cell.per_layer:
            value = cell.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        log(f"trace: read in {time.perf_counter() - t0:.3f} s")
    else:
        e2e = {"sims_per_s": completed / window_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    out["metrics"] = metrics
    walls = [r["end"] - r["start"] for r in records]
    log(f"window: {len(records)} requests in {window_s:.4f} s, median "
        f"request {statistics.median(walls):.4f} s; {completed} of "
        f"{attempted} sims ok")
    out["compared"] = compared
    check.print_compared(compared)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = cells.Cell.load(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, TPU_LOG_DIR))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX found {devices[0].platform!r}; the benchmark runs "
            f"only on the chip")
        return 2
    if len(devices) < cell.chips:
        log(f"{args.workload} needs {cell.chips} chips, JAX sees "
            f"{len(devices)}")
        return 2
    program.import_program(ROOT)
    out = run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
