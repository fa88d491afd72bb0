"""Sharded survey engine (DESIGN.md §9).

``ShardedGridRunner`` promotes ``BucketedGridRunner`` from a
single-device vmap into a multi-device batch engine: the (graphs x
points) grid of a bucket group is flattened to rows and the row axis is
sharded across a 1-D ``"grid"`` mesh (``launch.mesh.make_grid_mesh``)
via ``shard_map`` — each device runs the identical compiled per-row
program on its slice, so adding devices divides wall-clock without
changing any per-sim arithmetic (results are bit-identical to the vmap
path; ``tests/test_engine.py``).  Rows are streamed to devices through
``DoubleBufferQueue``, a depth-2 host->device prefetch queue: the
transfer for chunk k+1 is issued while chunk k computes.

Compile accounting (the survey's ``--assert-compiles`` contract) is
engine-invariant: the whole shard_map sits under one ``jax.jit``, and
every chunk is padded to an identical shape, so ``trace_counter`` sees
exactly one trace per (bucket, W, scheduler, netmodel) group no matter
the device count or chunking.  Warm starts come in two tiers:

* ``enable_compile_cache`` turns on JAX's *persistent* compilation
  cache so a fresh worker process re-traces but never re-compiles:
  fresh-vs-cached XLA compiles are counted by ``cache_counter`` (jit
  traces and cache misses are distinct odometers — a tier-1 warm
  worker shows ``traces == groups, misses == 0``).
* ``ExecutableStore`` (``exec_dir=``, or ``<cache root>/exec`` via
  ``make_grid_runner``) persists the *serialized compiled executable*
  per (program identity, argument shapes) key, so a tier-2 warm worker
  skips tracing too — it deserializes and runs: ``traces == 0,
  misses == 0, exec_counter().hits == groups``.  The survey's compile
  gate therefore checks ``traces + exec hits == groups``.

Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to get
8 host devices on CPU (README quick-start).
"""
from __future__ import annotations

import contextlib
import hashlib
import os
import pickle
import threading
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ...launch.mesh import make_grid_mesh
from .sim import BucketedGridRunner

__all__ = ["ShardedGridRunner", "DoubleBufferQueue", "make_sharded_rows_fn",
           "enable_compile_cache", "compile_cache_root", "cache_counter",
           "cache_event_counts", "SETUP_PHASES", "setup_span",
           "setup_seconds", "setup_timer",
           "ExecutableStore", "exec_counter"]


def make_sharded_rows_fn(run, mesh):
    """The engine's program shape, un-jitted: ``run(bspec, D, S, msd,
    dd, bw, seed, clusters)`` vmapped over the K cluster axis (last
    arg) and the leading rows axis (everything else), with the rows
    axis split across ``mesh``'s ``"grid"`` devices by ``shard_map``.
    Exposed separately so simlint's registry (``analysis.jaxpr_checks``)
    traces the very program ``ShardedGridRunner`` compiles."""
    # per row: vmap the K cluster signatures; per shard: vmap the
    # local rows; shard_map splits the row axis across devices.  No
    # collectives — each device's slice is independent, so the
    # varying-manual-axes check is moot (and must be off for the
    # while_loop body).
    over_clusters = jax.vmap(run, in_axes=(None,) * 7 + (0,))
    over_rows = jax.vmap(over_clusters, in_axes=(0,) * 7 + (None,))
    return jax.shard_map(over_rows, mesh=mesh,
                         in_specs=(P("grid"),) * 7 + (P(),),
                         out_specs=P("grid"), check_vma=False)


# ---------------------------------------------------------------------------
# persistent compile-cache accounting
#
# jax.monitoring has register-only listeners (no unregister), so a
# single module-level listener accumulates globally and ``cache_counter``
# reads deltas — the same scheme as sim.trace_counter.  jax emits one
# ``compile_requests_use_cache`` event per XLA compile attempt with the
# cache in use and one ``cache_hits`` event when the binary loads from
# it; there is no miss event, so misses = requests - hits.  In-process
# jit memoisation emits nothing — the counters describe cross-process
# warmth, not call counts.

_CACHE_EVENTS = {"hits": 0, "requests": 0}
_LISTENER = [False]


def _install_cache_listener():
    if _LISTENER[0]:
        return
    def _on_event(event, **kwargs):
        if event.endswith("/compilation_cache/cache_hits"):
            _CACHE_EVENTS["hits"] += 1
        elif event.endswith("/compile_requests_use_cache"):
            _CACHE_EVENTS["requests"] += 1
    jax.monitoring.register_event_listener(_on_event)
    _LISTENER[0] = True


CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: a fixed path, because the directory is part of
# what makes a later process find the entries (gitignored)
DEFAULT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), *[os.pardir] * 4,
    ".jax_cache"))


def compile_cache_root(cache_dir=None) -> str:
    """The one place the compile-cache directory is decided:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (and then no code sets
    another), else an explicit ``cache_dir``, else ``DEFAULT_CACHE_DIR``
    inside the checkout.  ``ExecutableStore`` entries live under
    ``<root>/exec``."""
    root = os.environ.get(CACHE_ENV) or (
        DEFAULT_CACHE_DIR if cache_dir is None else cache_dir)
    return os.path.abspath(os.path.expanduser(str(root)))


def enable_compile_cache(cache_dir=None) -> str:
    """Turn on JAX's persistent compilation cache at
    ``compile_cache_root(cache_dir)`` and drop the size/time floors so
    every simulator program is cached (our programs are small but cost
    seconds of XLA time).  A long-lived worker — or a restarted one —
    then answers survey requests with zero cold compiles: the second
    process pays tracing only and loads binaries from the cache.
    Idempotent; also installs the hit/miss listener so ``cache_counter``
    works.  Returns the cache root.

    The cache *singleton* latches on the first compile of the process —
    a dir configured afterwards is silently ignored — so a change of
    directory resets it (``compilation_cache.reset_cache``) to make
    enabling safe at any point, not just before the first jit.  Under
    ``$JAX_COMPILATION_CACHE_DIR`` JAX already holds that directory and
    nothing is reconfigured."""
    root = compile_cache_root(cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not os.environ.get(CACHE_ENV) and \
            jax.config.jax_compilation_cache_dir != root:
        jax.config.update("jax_compilation_cache_dir", root)
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    _install_cache_listener()
    return root


def cache_event_counts() -> dict:
    """Process-lifetime ``{"hits": int, "misses": int}`` totals."""
    return {"hits": _CACHE_EVENTS["hits"],
            "misses": _CACHE_EVENTS["requests"] - _CACHE_EVENTS["hits"]}


class cache_counter:
    """Scoped persistent-cache accounting: ``with cache_counter() as
    cc: ...; cc.hits, cc.misses``.  A *miss* is a fresh XLA compile
    (written to the cache when a dir is configured); a *hit* loaded a
    previously compiled binary.  jax's cache feature flag is on by
    default, so misses count fresh compiles even before
    ``enable_compile_cache`` — but nothing can *hit* until a dir is
    set.  Nests safely — delta-based, never resets the global
    accumulator."""

    def __enter__(self):
        _install_cache_listener()
        self._h0 = _CACHE_EVENTS["hits"]
        self._r0 = _CACHE_EVENTS["requests"]
        return self

    def __exit__(self, *exc):
        return False

    @property
    def hits(self) -> int:
        return _CACHE_EVENTS["hits"] - self._h0

    @property
    def misses(self) -> int:
        return ((_CACHE_EVENTS["requests"] - self._r0)
                - (_CACHE_EVENTS["hits"] - self._h0))


# ---------------------------------------------------------------------------
# set-up seconds by phase
#
# ``trace``: JAX tracing a program to a jaxpr and lowering it to MLIR;
# ``compile``: XLA compiles, compile-cache fetches, executable-store
# loads and saves; ``host``: the runner's own host work (encoding,
# padding and stacking graphs, imode estimates).  JAX reports its
# compile steps through jax.monitoring, a start scalar when a step opens
# and a duration when it closes; the program's own steps are
# ``setup_span``s.  Steps nest (a jnp function traced inside a program
# reports its own trace, the cache fetch sits inside the backend
# compile), so only a step that opens while no other is open counts,
# and no second is counted twice.  Same accumulate-and-diff scheme as
# ``cache_counter``.

SETUP_PHASES = ("trace", "compile", "host")
# jax's log_elapsed_time steps: a start scalar, then a duration
_JAX_STEPS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace",
    "/jax/core/compile/backend_compile_duration": "compile",
}
# a duration alone, reported from inside the backend compile step
_JAX_CACHE_FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"
_SETUP_SECONDS = dict.fromkeys(SETUP_PHASES, 0.0)
_OPEN_STEPS = threading.local()          # .depth: open steps, per thread


def _open_depth() -> int:
    return getattr(_OPEN_STEPS, "depth", 0)


def _step_closed(phase: str, seconds: float, opened: bool) -> None:
    """A step of ``phase`` ended: close it if it had opened, and count
    it when no step is left open around it."""
    depth = max(_open_depth() - 1, 0) if opened else _open_depth()
    _OPEN_STEPS.depth = depth
    if depth == 0:
        _SETUP_SECONDS[phase] += seconds


def _on_jax_step_start(event, value, **kwargs):
    if event in _JAX_STEPS:
        _OPEN_STEPS.depth = _open_depth() + 1


def _on_jax_step_duration(event, duration, **kwargs):
    if event in _JAX_STEPS:
        _step_closed(_JAX_STEPS[event], duration, True)
    elif event == _JAX_CACHE_FETCH:
        _step_closed("compile", duration, False)


jax.monitoring.register_scalar_listener(_on_jax_step_start)
jax.monitoring.register_event_duration_secs_listener(_on_jax_step_duration)


@contextlib.contextmanager
def setup_span(phase: str):
    """A set-up step of the program's own: a profiler span
    ``survey.<phase>``, so a trace taken around set-up shows it on the
    device trace's clock, whose seconds add to ``phase`` of
    ``setup_seconds``."""
    if phase not in SETUP_PHASES:
        raise KeyError(f"set-up phase {phase!r} is not one of "
                       f"{SETUP_PHASES}")
    _OPEN_STEPS.depth = _open_depth() + 1
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(f"survey.{phase}"):
            yield
    finally:
        _step_closed(phase, time.perf_counter() - t0, True)


def setup_seconds() -> dict:
    """Process-total set-up seconds by phase (``SETUP_PHASES``)."""
    return dict(_SETUP_SECONDS)


class setup_timer:
    """Scoped set-up accounting, mirroring ``cache_counter``: ``with
    setup_timer() as st: ...; st.seconds`` is ``{phase: seconds}``
    spent inside the block (valid during and after it).  Nests safely
    — delta-based, never resets the process totals."""

    def __enter__(self):
        self._s0 = setup_seconds()
        return self

    def __exit__(self, *exc):
        return False

    @property
    def seconds(self) -> dict:
        return {p: _SETUP_SECONDS[p] - self._s0[p] for p in SETUP_PHASES}


# ---------------------------------------------------------------------------
# tier-2 warm start: the serialized-executable store
#
# The persistent XLA cache (above) kills recompiles but a fresh process
# still pays the Python trace of every while_loop program — seconds per
# (scheduler, netmodel) group, and the dominant warm-worker cost on the
# mini grid.  ``ExecutableStore`` removes it: the AOT-compiled
# executable (``jit(f).lower(args).compile()``) is serialized with
# ``jax.experimental.serialize_executable`` and keyed by program
# identity + argument avals, so a warm worker deserializes and calls —
# zero traces, zero XLA compiles.

# bump to invalidate persisted entries; 2: the loop's named phases
# (SIM_PHASES) ride in the executable's HLO; 3: the flow frontier's
# backlog, and SimResult.backlog_peak among the outputs
_EXEC_FORMAT = 3
_EXEC_EVENTS = {"hits": 0, "misses": 0, "save_errors": 0}


class exec_counter:
    """Scoped ``ExecutableStore`` accounting, mirroring
    ``cache_counter``: ``with exec_counter() as xc: ...; xc.hits,
    xc.misses``.  A *hit* loaded a serialized executable (no trace, no
    XLA compile); a *miss* fell through to trace + compile (and then
    populated the store); a *save error* is a compiled executable the
    store failed to persist, so the next worker will miss too.
    In-process reuse of an already-resolved executable counts
    nothing."""

    def __enter__(self):
        self._h0 = _EXEC_EVENTS["hits"]
        self._m0 = _EXEC_EVENTS["misses"]
        self._e0 = _EXEC_EVENTS["save_errors"]
        return self

    def __exit__(self, *exc):
        return False

    @property
    def hits(self) -> int:
        return _EXEC_EVENTS["hits"] - self._h0

    @property
    def misses(self) -> int:
        return _EXEC_EVENTS["misses"] - self._m0

    @property
    def save_errors(self) -> int:
        return _EXEC_EVENTS["save_errors"] - self._e0


class ExecutableStore:
    """Directory-backed store of serialized compiled executables.

    ``load(key)`` returns a callable ``jax.stages.Loaded`` executable
    or ``None``; ``save(key, compiled)`` persists an AOT-compiled
    program.  Keys must name the *program*, not just the shapes — the
    runner's key includes scheduler, netmodel, max_steps, device count,
    backend, jax version and the full argument aval signature, plus
    ``_EXEC_FORMAT`` so a code change can invalidate every entry at
    once.  Any load failure (missing file, corrupt pickle, foreign
    device topology) degrades to a miss: the caller recompiles and
    overwrites, so a stale store can slow a worker down but never
    change its results."""

    def __init__(self, path):
        self.path = os.path.expanduser(str(path))
        os.makedirs(self.path, exist_ok=True)

    def _file(self, key):
        digest = hashlib.sha256(repr(key).encode()).hexdigest()[:32]
        return os.path.join(self.path, digest + ".jexec")

    def load(self, key):
        from jax.experimental.serialize_executable import \
            deserialize_and_load
        try:
            with setup_span("compile"), open(self._file(key), "rb") as f:
                payload, in_tree, out_tree = pickle.load(f)
                loaded = deserialize_and_load(payload, in_tree, out_tree)
        except Exception:
            _EXEC_EVENTS["misses"] += 1
            return None
        _EXEC_EVENTS["hits"] += 1
        return loaded

    def save(self, key, compiled) -> None:
        from jax.experimental.serialize_executable import serialize
        try:
            with setup_span("compile"):
                payload, in_tree, out_tree = serialize(compiled)
                tmp = self._file(key) + f".tmp{os.getpid()}"
                with open(tmp, "wb") as f:
                    pickle.dump((payload, in_tree, out_tree), f)
                os.replace(tmp, self._file(key))
        except Exception:        # best-effort cache; never fail the run,
            _EXEC_EVENTS["save_errors"] += 1     # but count the miss-to-be


# ---------------------------------------------------------------------------
# double-buffered host->device streaming

_EMPTY = object()


class DoubleBufferQueue:
    """Depth-2 prefetch iterator: ``put`` (e.g. a sharded
    ``jax.device_put``) is applied to batch k+1 before batch k is
    handed to the consumer, so the k+1 transfer overlaps the k compute
    (both are async dispatches).  Invariants (tested):

    * batches come out in input order, each exactly once — including
      the last batch, which drains with no trailing ``put``;
    * at most two batches are resident (the one consumed + the one
      prefetching);
    * empty and single-batch inputs degrade gracefully.
    """

    def __init__(self, batches, put=None):
        self._it = iter(batches)
        self._put = (lambda x: x) if put is None else put
        self._ahead = _EMPTY
        self._advance()

    def _advance(self):
        try:
            self._ahead = self._put(next(self._it))
        except StopIteration:
            self._ahead = _EMPTY

    def __iter__(self):
        return self

    def __next__(self):
        if self._ahead is _EMPTY:
            raise StopIteration
        current = self._ahead
        self._advance()   # issue the next transfer before k is consumed
        return current


# ---------------------------------------------------------------------------
# the sharded runner

class ShardedGridRunner(BucketedGridRunner):
    """``BucketedGridRunner`` with the (graphs x points) grid sharded
    across a 1-D device mesh.

    Layout: the [B graphs, N points] grid flattens to G = B*N rows in
    b-major order (row g = b*N + n), each row carrying its own padded
    spec + estimates + point scalars; rows are padded up to a multiple
    of the device count by repeating row 0 (valid sims, sliced off the
    results) and split evenly by ``shard_map`` over the ``"grid"``
    axis.  The K-cluster axis stays an inner vmap with the cores matrix
    replicated, so results keep the vmap path's ``SimResult[K, B, N]``
    shape and bit pattern.

    ``stream_rows`` chunks the row axis: every chunk is padded to the
    same shape (one compile) and flows through ``DoubleBufferQueue`` so
    host->device transfer of chunk k+1 overlaps compute of chunk k —
    bounding device-resident bytes for grids larger than memory.

    ``devices=n`` shards over the first n visible devices
    (``make_grid_mesh``); default all of them.  Pass ``mesh`` to share
    one mesh across many runners.

    ``exec_dir`` points at an ``ExecutableStore`` (tier-2 warm start):
    the first call per argument signature loads the serialized compiled
    executable instead of tracing + compiling — or, on a miss,
    AOT-compiles (bit-identical to the jit path), saves, and proceeds.
    """

    def __init__(self, entries, scheduler, n_workers, cores,
                 netmodel="maxmin", max_steps=None, shape=None,
                 batch=None, est_cache=None, *, mesh=None, devices=None,
                 stream_rows=None, exec_dir=None):
        self.mesh = make_grid_mesh(devices) if mesh is None else mesh
        if "grid" not in self.mesh.axis_names:
            raise ValueError(f"mesh axes {self.mesh.axis_names} lack the "
                             f"'grid' axis — build with make_grid_mesh()")
        self.n_devices = int(self.mesh.devices.size)
        self.stream_rows = None if stream_rows is None else int(stream_rows)
        self._store = None if exec_dir is None else ExecutableStore(exec_dir)
        self._aot = {}           # aval signature -> resolved executable
        self._program_id = (str(scheduler), str(netmodel),
                            None if max_steps is None else int(max_steps))
        super().__init__(entries, scheduler, n_workers, cores,
                         netmodel=netmodel, max_steps=max_steps,
                         shape=shape, batch=batch, est_cache=est_cache)

    def _make_fn(self):
        return jax.jit(make_sharded_rows_fn(self.run, self.mesh))

    def _resolve_exec(self, batch, clusters_dev):
        """The executable for one chunk signature: in-process memo ->
        store load -> AOT trace + compile (+ store save)."""
        sig = repr(jax.tree_util.tree_map(
            lambda x: (tuple(x.shape), str(x.dtype)),
            (batch, clusters_dev)))
        fn = self._aot.get(sig)
        if fn is not None:
            return fn
        key = ("repro-exec", _EXEC_FORMAT, jax.__version__,
               jax.default_backend(), self.n_devices,
               self._program_id, sig)
        fn = self._store.load(key)
        if fn is None:
            fn = self._fn.lower(*batch, clusters_dev).compile()
            self._store.save(key, fn)
        self._aot[sig] = fn
        return fn

    def _row_chunks(self, G):
        """(chunk_rows, padded_G): chunk a multiple of the device
        count, every chunk identically sized so one compile serves
        all."""
        d = self.n_devices
        if self.stream_rows is None:
            chunk = -(-G // d) * d
        else:
            chunk = max(1, -(-self.stream_rows // d)) * d
        return chunk, -(-G // chunk) * chunk

    def chunk_outputs(self, D, S, M, DD, BW, SD):
        """Run the grid; returns the device-resident ``SimResult`` of
        each row chunk, in row order, each sharded over the mesh —
        ``gather`` assembles them into ``SimResult[K, B, N]``."""
        tm = jax.tree_util.tree_map
        B, N = D.shape[:2]
        G = B * N
        chunk, gp = self._row_chunks(G)

        def rowize(x, reps):       # [B,...] -> [G,...] b-major, + pad
            x = np.asarray(x)
            x = np.repeat(x, reps, axis=0) if reps > 1 else x
            if gp > x.shape[0]:
                fill = np.broadcast_to(x[:1],
                                       (gp - x.shape[0],) + x.shape[1:])
                x = np.concatenate([x, fill], axis=0)
            return x

        spec_rows = tm(lambda x: rowize(x, N), self.bspec)
        D_r = rowize(np.asarray(D).reshape((G,) + D.shape[2:]), 1)
        S_r = rowize(np.asarray(S).reshape((G,) + S.shape[2:]), 1)
        M_r, DD_r, BW_r, SD_r = (rowize(np.tile(np.asarray(v), B), 1)
                                 for v in (M, DD, BW, SD))

        row_shard = NamedSharding(self.mesh, P("grid"))
        clusters_dev = jax.device_put(self.clusters,
                                      NamedSharding(self.mesh, P()))
        args = (spec_rows, D_r, S_r, M_r, DD_r, BW_r, SD_r)

        def chunks():
            for i in range(gp // chunk):
                sl = slice(i * chunk, (i + 1) * chunk)
                yield tm(lambda x: x[sl], args)

        outs, fn = [], self._fn
        for i, batch in enumerate(DoubleBufferQueue(
                chunks(), put=lambda b: jax.device_put(b, row_shard))):
            if i == 0 and self._store is not None:
                fn = self._resolve_exec(batch, clusters_dev)
            outs.append(fn(*batch, clusters_dev))
        return outs

    def _execute(self, D, S, M, DD, BW, SD):
        return self.gather(self.chunk_outputs(D, S, M, DD, BW, SD),
                           *D.shape[:2])

    @staticmethod
    def gather(outs, B, N):
        """The host ``SimResult[K, B, N]`` of ``chunk_outputs``' chunks
        for a grid of B graphs x N points."""
        tm = jax.tree_util.tree_map
        res = tm(lambda *xs: np.concatenate([np.asarray(x) for x in xs],
                                            axis=0), *outs)

        def to_grid(x):            # [G(+pad), K] -> [K, B, N]
            x = x[:B * N].reshape((B, N) + x.shape[1:])
            return np.moveaxis(x, 2, 0)
        return tm(to_grid, res)
