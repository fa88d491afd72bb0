"""One front door for the vectorized simulator family.

``build`` normalizes the per-factory kwarg sprawl into a single entry
point: pick static vs dynamic, bucket-form vs per-graph-bound, and
carry every tuning knob in a frozen ``SimConfig``.  The ``make_*``
factories in ``sim.py``/``scheduling.py`` stay as thin delegating
wrappers; the full argument contract lives in DESIGN.md §8.

    from repro.core.vectorized.api import build, SimConfig

    run = build(spec, n_workers=4, cores=2)            # static sim
    res = run(assignment, priority)                    # -> SimResult

    sched = build(spec, n_workers=4, cores=2, scheduler="blevel")
    a, p = sched(est_dur, est_size, bandwidth, seed)

    dyn = build(spec, n_workers=4, cores=2, scheduler="greedy",
                dynamic=True, config=SimConfig(msd=1.0))
    res = dyn(est_dur, est_size)                       # msd baked in

``spec=None`` returns the late-bound bucket form (the spec becomes the
first traced argument) — what ``BucketedGridRunner`` and the survey
compile once per shape bucket.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

import numpy as np

from .specs import GraphSpec, as_bucketed, frontier_caps_for_spec
from . import sim as _sim
from . import scheduling as _scheduling


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Frozen bundle of every simulator/scheduler option ``build``
    accepts (hashable, so configs can key caches).  ``flow_slots`` /
    ``frontier`` are tri-state like the factory kwargs (``None`` =
    default-on where supported, DESIGN.md §3); ``msd`` /
    ``decision_delay`` / ``imode`` / ``seed`` become the *default*
    call arguments of a bound dynamic run — each can still be
    overridden per call or swept under ``vmap``.

    The engine block (DESIGN.md §9): ``engine`` picks the grid
    executor for ``make_grid_runner`` (``"vmap"`` single-device, or
    ``"sharded"`` across ``devices`` mesh devices with optional
    ``stream_rows``-row double-buffered chunking); ``cache_dir``
    enables JAX's persistent compilation cache for *every* entry point
    that sees the config, so warm worker processes skip XLA
    compilation entirely (``$JAX_COMPILATION_CACHE_DIR``, when set,
    takes precedence — ``engine.compile_cache_root``)."""

    flow_slots: bool | None = None
    frontier: bool | None = None
    frontier_caps: tuple[int, int] | None = None
    waterfill_impl: str = "auto"
    flow_rounds: int = 4
    max_steps: int | None = None
    msd: float = 0.0
    decision_delay: float = 0.0
    imode: str = "exact"
    seed: int = 0
    engine: str = "vmap"
    devices: int | None = None
    stream_rows: int | None = None
    cache_dir: str | None = None

    def replace(self, **kwargs) -> "SimConfig":
        return dataclasses.replace(self, **kwargs)


def _merge_config(config, opts) -> SimConfig:
    cfg = SimConfig() if config is None else config
    if opts:
        unknown = set(opts) - {f.name for f in dataclasses.fields(SimConfig)}
        if unknown:
            raise TypeError(f"build() got unknown option(s) "
                            f"{sorted(unknown)}; SimConfig fields are "
                            f"{sorted(f.name for f in dataclasses.fields(SimConfig))}")
        cfg = cfg.replace(**opts)
    return cfg


def build(spec=None, *, n_workers: int, cores=None, scheduler=None,
          netmodel: str = "maxmin", dynamic: bool = False,
          max_cores: int | None = None, config: SimConfig | None = None,
          **opts):
    """Build a simulator or scheduler callable (DESIGN.md §8).

    Dispatch:

    * ``scheduler=None`` (default) — the **static simulator**:
      ``run(assignment, priority, ...) -> SimResult``.
    * ``dynamic=True`` — the **dynamic simulator** for ``scheduler``
      (default ``"blevel"``): ``run(est_durations, est_sizes, ...) ->
      SimResult``.
    * ``scheduler`` given with ``dynamic=False`` — the **static
      schedule function**: ``schedule(est_durations, est_sizes,
      bandwidth, seed[, cores]) -> (assignment, priority)``.

    ``spec`` may be a ``GraphSpec``/``BucketedGraphSpec`` (bound now:
    the spec argument disappears from the returned callable) or
    ``None`` (bucket form: the callable takes the spec as its first
    traced argument, one compile per shape bucket).  Options come from
    ``config`` (a ``SimConfig``) and/or keyword overrides — ``build(...,
    frontier=False)`` is shorthand for
    ``config=SimConfig(frontier=False)``.  ``cores=None`` plus a static
    ``max_cores`` keeps the cluster a traced call-time argument."""
    cfg = _merge_config(config, opts)
    if cfg.cache_dir is not None:
        from .engine import enable_compile_cache
        enable_compile_cache(cfg.cache_dir)
    bspec = None if spec is None else as_bucketed(spec)
    if (bspec is not None and cfg.frontier is not False
            and cfg.frontier_caps is None
            and isinstance(bspec.n_inputs, np.ndarray)):
        # the spec is concrete, so widen the shape-derived caps to the
        # root count — all roots are ready at t=0 (specs.py)
        cfg = cfg.replace(frontier_caps=frontier_caps_for_spec(
            bspec, n_workers=n_workers))
    if bspec is not None and cores is not None:
        # host-side guard: a task that fits no worker would stall the
        # event loop — raise here like the reference scheduler base
        _sim._check_cpus_fit([bspec],
                             _sim._resolve_cores(n_workers, cores),
                             "build")

    if scheduler is not None and not dynamic:
        fn = _scheduling.make_bucket_scheduler(n_workers, cores, scheduler,
                                               max_cores)
        if bspec is None:
            return fn
        return lambda est_dur, est_size, bandwidth, seed=jnp.int32(0), \
            cores=None: fn(bspec, est_dur, est_size, bandwidth, seed, cores)

    if dynamic:
        brun = _sim.make_bucket_dynamic_simulator(
            n_workers, cores, scheduler or "blevel", netmodel,
            cfg.flow_rounds, cfg.max_steps, max_cores=max_cores,
            flow_slots=cfg.flow_slots, frontier=cfg.frontier,
            frontier_caps=cfg.frontier_caps,
            waterfill_impl=cfg.waterfill_impl)
        if bspec is None:
            return brun

        def run(est_durations, est_sizes,
                msd=jnp.float32(cfg.msd),
                decision_delay=jnp.float32(cfg.decision_delay),
                bandwidth=jnp.float32(100 * 1024 * 1024),
                seed=jnp.int32(cfg.seed), cores=None):
            return brun(bspec, est_durations, est_sizes, msd,
                        decision_delay, bandwidth, seed, cores)
        return run

    brun = _sim.make_bucket_simulator(
        n_workers, cores, netmodel, cfg.flow_rounds, cfg.max_steps,
        max_cores=max_cores, flow_slots=cfg.flow_slots,
        frontier=cfg.frontier, frontier_caps=cfg.frontier_caps,
        waterfill_impl=cfg.waterfill_impl)
    if bspec is None:
        return brun

    def run(assignment, priority, durations=None, sizes=None,
            bandwidth=jnp.float32(100 * 1024 * 1024), cores=None):
        return brun(bspec, assignment, priority, durations, sizes,
                    bandwidth, cores)
    return run


def make_grid_runner(entries, scheduler, n_workers, cores, *,
                     netmodel: str = "maxmin", max_steps: int | None = None,
                     shape=None, batch=None, est_cache=None,
                     config: SimConfig | None = None, **opts):
    """Engine-dispatching front door over the bucket grid runners
    (DESIGN.md §9).  Positional arguments match
    ``BucketedGridRunner``; the engine choice rides the same
    config/override mechanics as ``build``::

        runner = make_grid_runner(entries, "blevel", 8, cores2d,
                                  engine="sharded", devices=8,
                                  cache_dir=".jax_cache")
        ms, xfer = runner(points)          # [K, B, N], sharded

    ``engine="vmap"`` (default) returns a plain ``BucketedGridRunner``;
    ``engine="sharded"`` returns a ``ShardedGridRunner`` over
    ``devices`` mesh devices with optional ``stream_rows`` chunking.
    ``cache_dir`` enables the persistent compilation cache either way,
    and for the sharded engine additionally an ``ExecutableStore``
    under ``<cache root>/exec`` — a warm worker then skips tracing
    entirely (DESIGN.md §9).  ``$JAX_COMPILATION_CACHE_DIR``, when
    set, is the cache root whatever ``cache_dir`` says."""
    cfg = _merge_config(config, opts)
    cache_root = None
    if cfg.cache_dir is not None:
        from .engine import enable_compile_cache
        cache_root = enable_compile_cache(cfg.cache_dir)
    kwargs = dict(netmodel=netmodel, shape=shape, batch=batch,
                  est_cache=est_cache,
                  max_steps=cfg.max_steps if max_steps is None else max_steps)
    if cfg.engine == "vmap":
        return _sim.BucketedGridRunner(entries, scheduler, n_workers,
                                       cores, **kwargs)
    if cfg.engine == "sharded":
        import os
        from .engine import ShardedGridRunner
        exec_dir = (None if cache_root is None else
                    os.path.join(cache_root, "exec"))
        return ShardedGridRunner(entries, scheduler, n_workers, cores,
                                 devices=cfg.devices,
                                 stream_rows=cfg.stream_rows,
                                 exec_dir=exec_dir, **kwargs)
    raise TypeError(f"unknown engine {cfg.engine!r}; SimConfig.engine is "
                    f"'vmap' or 'sharded'")


def build_for_graph(graph, **kwargs):
    """``build`` for a ``TaskGraph``: encodes the graph first."""
    from .specs import encode_graph
    return build(encode_graph(graph), **kwargs)


__all__ = ["SimConfig", "build", "build_for_graph", "make_grid_runner",
           "GraphSpec"]
