"""In-loop vectorized schedulers for the dynamic JAX simulator
(DESIGN.md §3).

These are the dense-array counterparts of the deterministic reference
schedulers in ``repro.core.schedulers.det`` — same decisions, expressed as
fixed-shape JAX ops so a whole (graph x scheduler x msd x imode) grid runs
under one ``jax.vmap``.  ``VEC_SCHEDULERS`` maps each name to its kind:

* ``"static"`` entries compute the whole ``task -> worker`` map plus
  priorities from the t=0 imode estimates in one invocation
  (``make_vec_scheduler`` returns the schedule function):

  - ``blevel`` — blevel/HLFET list order (mirrors ``blevel-det``);
  - ``tlevel`` — SCFET, ascending t-level (mirrors ``tlevel-det``);
  - ``mcp``    — simplified MCP, ascending ALAP (mirrors ``mcp-det``;
    with ALAP = CP - blevel this order coincides with ``blevel`` — kept
    as its own entry so the registry mirrors the stochastic family);
  - ``etf``    — ETF/DLS-style placer: at every step commit the
    (frontier task, worker) pair with the earliest estimated start
    (mirrors ``etf-det``);
  - ``random`` — counter-based, seed-parameterized uniform choice over
    eligible workers (mirrors ``random-det``; the seed is a traced
    argument, so a whole seed batch runs under one ``vmap``).

* ``"dynamic"`` entries run on every (MSD-gated) scheduler invocation:

  - ``greedy`` — ws-style greedy worker selection: each ready task goes
    to the worker with minimal (estimated transfer cost, queued load,
    id) (mirrors ``greedy``; no work stealing).

Every scheduler exists in two bindings sharing one implementation:

* the ``make_bucket_*`` factories close over the *cluster* only
  (``cores: i32[W]``, zero-core entries = padded/absent workers) and
  take the graph as a runtime ``BucketedGraphSpec`` argument — so one
  jit trace serves every graph in a shape bucket, and the batch axis of
  a stacked bucket vmaps straight through;
* the legacy ``make_vec_scheduler``/``make_static_*`` factories bind a
  single unpadded ``GraphSpec`` at build time (the per-graph path).

Mask semantics: invalid edges never contribute to levels, readiness
counts, data-ready times or transfer costs; invalid tasks are committed
as no-ops (zero duration, one core, the value written back to a
worker's earliest slot equals the value read, so real placements are
untouched) and their assignments are discarded by the simulator.
Indistinguishable decisions are broken by the smallest index instead of
the RNG the stochastic reference schedulers use — both sides of the
parity tests (``tests/test_vectorized_dynamic.py``) share that rule.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .specs import as_bucketed, as_jax

# name -> kind; membership == "has a vectorized in-loop implementation"
VEC_SCHEDULERS = {
    "blevel": "static",
    "tlevel": "static",
    "mcp": "static",
    "etf": "static",
    "random": "static",
    "greedy": "dynamic",
}

NEG = jnp.float32(-3e38)


def spmd_safe_sort(row):
    """Ascending sort of a small NaN-free 1-D float row without
    emitting a ``sort`` HLO.  XLA's CPU SPMD partitioner mis-partitions
    ``sort`` ops that sit inside loop bodies under ``shard_map`` manual
    regions: it inserts cross-partition all-reduces that *sum* live
    values across devices, silently corrupting every shard (pinned by
    ``tests/test_engine.py``; DESIGN.md §9).  Rank-and-scatter over
    pairwise comparisons is bitwise-equivalent for NaN-free input —
    ties are bitwise-identical values, so their placement order cannot
    matter — and costs O(n²) on rows of at most ``max_cores``
    entries."""
    n = row.shape[0]
    ids = jnp.arange(n)
    lt = row[None, :] < row[:, None]
    tie = (row[None, :] == row[:, None]) & (ids[None, :] < ids[:, None])
    rank = jnp.sum(lt | tie, axis=1)
    return jnp.zeros_like(row).at[rank].set(row)


def spmd_safe_argsort(key):
    """Stable ascending argsort (``jnp.argsort(key, stable=True)``) for
    NaN-free keys, built from the same rank-and-scatter trick as
    ``spmd_safe_sort`` and for the same reason: scheduler order
    computations run inside the simulator's event loop, where a
    ``sort`` HLO under ``shard_map`` triggers the CPU SPMD
    partitioner's cross-device all-reduce bug.  rank(i) counts strictly
    smaller keys plus equal keys at smaller indices, which is exactly
    the stable order; scattering indices by rank inverts it."""
    n = key.shape[0]
    ids = jnp.arange(n)
    lt = key[None, :] < key[:, None]
    tie = (key[None, :] == key[:, None]) & (ids[None, :] < ids[:, None])
    rank = jnp.sum(lt | tie, axis=1)
    return jnp.zeros(n, ids.dtype).at[rank].set(ids)


def _resolve_cores(n_workers, cores):
    """Per-worker core vector: broadcast a scalar, pass vectors through.
    Zero-core entries are inert padding (no task fits, no slot opens).
    ``None`` passes through — the traced-cores binding, where the
    cluster arrives as a runtime argument instead (DESIGN.md §3)."""
    if cores is None:
        return None
    return np.broadcast_to(np.asarray(cores, np.int32), (n_workers,)).copy()


def _static_max_cores(cores_default, max_cores):
    """The static core-count bound (python int) that sizes per-worker
    slot timelines and start loops; with a traced cores vector it must
    be supplied explicitly since the values are unknown at trace time."""
    if max_cores is not None:
        return max(int(max_cores), 1)
    if cores_default is None:
        raise ValueError("max_cores is required when cores is None (the "
                         "traced-cores binding has no values to bound at "
                         "build time)")
    return max(int(cores_default.max()), 1)


def _cores_arg(cores, cores_default):
    """The cluster actually used by one call: the runtime ``cores``
    argument (traced — one compiled program serves every same-W
    cluster), falling back to the build-time vector."""
    if cores is None:
        if cores_default is None:
            raise ValueError("built without a cluster: pass cores at call "
                             "time")
        cores = cores_default
    return jnp.asarray(cores, jnp.int32)


def bucket_blevel(bspec, est_dur):
    """b-level from *estimated* durations (imode view at t=0); task ids
    are a topological order by construction (``TaskGraph.new_task``), so
    one reverse sweep suffices.  Invalid edges are masked out, so padded
    tasks keep b-level 0 and real levels match the unpadded graph."""
    bspec = as_jax(bspec)
    T = bspec.T
    e_task, e_obj = bspec.edge_task, bspec.edge_obj
    producer, edge_valid = bspec.producer, bspec.edge_valid

    def body(i, bl):
        t = T - 1 - i
        child = jnp.max(jnp.where((producer[e_obj] == t) & edge_valid,
                                  bl[e_task], 0.0), initial=0.0)
        return bl.at[t].set(est_dur[t] + child)

    return jax.lax.fori_loop(0, T, body, jnp.zeros(T, jnp.float32))


def bucket_tlevel(bspec, est_dur):
    """t-level (earliest possible start ignoring comm costs) from
    estimated durations; forward sweep over the id-topological order."""
    bspec = as_jax(bspec)
    T = bspec.T
    e_task, e_obj = bspec.edge_task, bspec.edge_obj
    producer, edge_valid = bspec.producer, bspec.edge_valid

    def body(t, tl):
        par = producer[e_obj]
        reach = jnp.max(jnp.where((e_task == t) & edge_valid,
                                  tl[par] + est_dur[par], 0.0), initial=0.0)
        return tl.at[t].set(reach)

    return jax.lax.fori_loop(0, T, body, jnp.zeros(T, jnp.float32))


def make_blevel_fn(spec):
    """Legacy binding: close over one graph, return ``blevel(est_dur)``."""
    b = as_bucketed(spec)
    return lambda est_dur: bucket_blevel(b, est_dur)


def make_tlevel_fn(spec):
    """Legacy binding: close over one graph, return ``tlevel(est_dur)``."""
    b = as_bucketed(spec)
    return lambda est_dur: bucket_tlevel(b, est_dur)


def rank_priorities(bl):
    """priority = T - rank in decreasing-b-level order (ties: smaller id).
    Globally distinct, so downstream worker/download tie-breaks never
    depend on float equality.  Padded tasks (b-level 0, largest ids)
    rank last, so real priorities keep their relative order."""
    T = bl.shape[0]
    order = spmd_safe_argsort(-bl)
    return (jnp.zeros(T, jnp.float32)
            .at[order].set(jnp.float32(T) - jnp.arange(T, dtype=jnp.float32)))


def _make_bucket_list_scheduler(n_workers, cores, order_fn, max_cores=None):
    """Shared static list-scheduling machinery: commit tasks in the order
    ``order_fn(bspec, est_dur) -> i32[T]`` (rank -> task id), each to the
    earliest-start worker.

    Returns ``schedule(bspec, est_durations, est_sizes, bandwidth, seed,
    cores) -> (assignment i32[T], priority f32[T])`` — pure JAX, vmap-able
    over the spec batch axis, the estimate arrays (imodes), bandwidth,
    seed (ignored here; the uniform signature keeps every static
    scheduler batchable the same way) and the per-worker ``cores``
    vector (traced: one compiled program serves every same-W cluster;
    ``None`` falls back to the build-time cluster).

    Worker selection is the earliest-start estimate over per-core free
    times with uncontended transfer costs, committed task by task — the
    same timeline model as ``schedulers.base.EarliestStartPlacer``.
    Padded tasks commit with zero duration into a worker's earliest slot
    (a no-op on the timeline); padded edges never feed data-ready times.
    """
    W = n_workers
    cores_default = _resolve_cores(n_workers, cores)
    C = _static_max_cores(cores_default, max_cores)
    w_ids = jnp.arange(W)

    def schedule(bspec, est_dur, est_size, bandwidth, seed=jnp.int32(0),
                 cores=None):
        del seed
        cores_j = _cores_arg(cores, cores_default)
        bspec = as_jax(bspec)
        T = bspec.T
        e_task, e_obj = bspec.edge_task, bspec.edge_obj
        producer, edge_valid = bspec.producer, bspec.edge_valid
        cpus = bspec.cpus
        est_dur = jnp.asarray(est_dur, jnp.float32)
        est_size = jnp.asarray(est_size, jnp.float32)
        bandwidth = jnp.asarray(bandwidth, jnp.float32)
        order = order_fn(bspec, est_dur)            # rank -> task id
        # per-worker core free times, sorted ascending; slots past a
        # worker's core count are pinned at +inf
        slots0 = jnp.where(jnp.arange(C)[None, :] < cores_j[:, None],
                           0.0, jnp.inf).astype(jnp.float32)
        xfer = est_size[e_obj] / bandwidth          # f32[E]

        def body(r, st):
            slots, aw, fin, prio = st
            t = order[r]
            pw = aw[producer[e_obj]]                # parents placed earlier
            pf = fin[producer[e_obj]]
            ready_ew = pf[:, None] + jnp.where(
                pw[:, None] == w_ids[None, :], 0.0, xfer[:, None])
            mine = (e_task == t) & edge_valid
            data_ready = jnp.max(jnp.where(mine[:, None], ready_ew, 0.0),
                                 axis=0, initial=0.0)
            core_ready = slots[:, cpus[t] - 1]      # cpus-th smallest
            est = jnp.maximum(core_ready, data_ready)
            est = jnp.where(cores_j >= cpus[t], est, jnp.inf)
            w = jnp.argmin(est)                     # ties: smallest id
            finish = est[w] + est_dur[t]
            row = jnp.where(jnp.arange(C) < cpus[t], finish, slots[w])
            slots = slots.at[w].set(spmd_safe_sort(row))
            return (slots, aw.at[t].set(w.astype(jnp.int32)),
                    fin.at[t].set(finish),
                    prio.at[t].set(jnp.float32(T) - r.astype(jnp.float32)))

        _, aw, _, prio = jax.lax.fori_loop(
            0, T, body, (slots0, jnp.zeros(T, jnp.int32),
                         jnp.zeros(T, jnp.float32),
                         jnp.zeros(T, jnp.float32)))
        return aw, prio

    return schedule


def make_bucket_blevel_scheduler(n_workers, cores, max_cores=None):
    """blevel/HLFET: decreasing estimated b-level (ties: smaller id).
    Decreasing b-level is topological for positive durations, so no
    repair pass is needed (mirrors ``DetBlevelScheduler``)."""
    def order_fn(bspec, est_dur):
        return spmd_safe_argsort(-bucket_blevel(bspec, est_dur))

    return _make_bucket_list_scheduler(n_workers, cores, order_fn,
                                       max_cores)


def make_bucket_tlevel_scheduler(n_workers, cores, max_cores=None):
    """tlevel/SCFET: ascending estimated t-level (ties: smaller id);
    topological for positive durations (mirrors ``DetTlevelScheduler``)."""
    def order_fn(bspec, est_dur):
        return spmd_safe_argsort(bucket_tlevel(bspec, est_dur))

    return _make_bucket_list_scheduler(n_workers, cores, order_fn,
                                       max_cores)


def make_bucket_mcp_scheduler(n_workers, cores, max_cores=None):
    """Simplified MCP: ascending ALAP = CP - blevel (ties: smaller id) —
    the same simplification as the reference ``MCPScheduler`` (mirrors
    ``DetMCPScheduler``)."""
    def order_fn(bspec, est_dur):
        bl = bucket_blevel(bspec, est_dur)
        # padded tasks have b-level 0, so the unmasked max is the true CP
        return spmd_safe_argsort(jnp.max(bl) - bl)  # simlint: disable=PY205

    return _make_bucket_list_scheduler(n_workers, cores, order_fn,
                                       max_cores)


def make_bucket_etf_scheduler(n_workers, cores, max_cores=None):
    """ETF/DLS-style earliest-finish placer: at every step pick, over all
    frontier tasks (parents already committed) and eligible workers, the
    pair with the lexicographically smallest (estimated start, -b-level,
    task id, worker id) and commit it (mirrors ``DetETFScheduler``).

    Same ``schedule(bspec, est_dur, est_size, bandwidth, seed, cores)``
    signature as the list schedulers; T committing steps, each scanning
    the dense [T, W] estimate matrix.  Padded tasks are permanent
    zero-cost frontier members; committing one writes a worker's
    earliest slot back unchanged, so real pair choices are unaffected.
    """
    W = n_workers
    cores_default = _resolve_cores(n_workers, cores)
    C = _static_max_cores(cores_default, max_cores)

    def schedule(bspec, est_dur, est_size, bandwidth, seed=jnp.int32(0),
                 cores=None):
        del seed
        cores_j = _cores_arg(cores, cores_default)
        bspec = as_jax(bspec)
        T = bspec.T
        e_task, e_obj = bspec.edge_task, bspec.edge_obj
        producer, edge_valid = bspec.producer, bspec.edge_valid
        n_inputs, cpus = bspec.n_inputs, bspec.cpus
        est_dur = jnp.asarray(est_dur, jnp.float32)
        est_size = jnp.asarray(est_size, jnp.float32)
        bandwidth = jnp.asarray(bandwidth, jnp.float32)
        bl = bucket_blevel(bspec, est_dur)
        slots0 = jnp.where(jnp.arange(C)[None, :] < cores_j[:, None],
                           0.0, jnp.inf).astype(jnp.float32)
        xfer = est_size[e_obj] / bandwidth          # f32[E]
        eligible_tw = cores_j[None, :] >= cpus[:, None]       # [T, W]
        evf = edge_valid.astype(jnp.int32)

        def body(r, st):
            slots, aw, fin, done, prio = st
            par = producer[e_obj]
            cnt = (jnp.zeros(T, jnp.int32)
                   .at[e_task].add(done[par].astype(jnp.int32) * evf))
            frontier = ~done & (cnt >= n_inputs)
            pw, pf = aw[par], fin[par]
            ready_ew = pf[:, None] + jnp.where(
                pw[:, None] == jnp.arange(W)[None, :], 0.0, xfer[:, None])
            ready_ew = jnp.where(edge_valid[:, None], ready_ew, 0.0)
            data_ready = (jnp.zeros((T, W), jnp.float32)
                          .at[e_task].max(ready_ew))
            core_ready = slots[:, cpus - 1].T       # [T, W]
            est = jnp.maximum(core_ready, data_ready)
            est = jnp.where(frontier[:, None] & eligible_tw, est, jnp.inf)
            # lexicographic min of (est, -bl, task id, worker id)
            flat_est = est.reshape(-1)
            # est is inf outside frontier x eligible; padded tasks are
            # zero-cost frontier members whose commits are no-ops
            cand = flat_est == jnp.min(flat_est)  # simlint: disable=PY205
            flat_bl = jnp.broadcast_to(bl[:, None], (T, W)).reshape(-1)
            key = jnp.where(cand, flat_bl, NEG)
            cand = cand & (key == jnp.max(key))  # simlint: disable=PY205
            idx = jnp.argmax(cand)                  # first = smallest (t, w)
            t, w = idx // W, idx % W
            finish = flat_est[idx] + est_dur[t]
            row = jnp.where(jnp.arange(C) < cpus[t], finish, slots[w])
            slots = slots.at[w].set(spmd_safe_sort(row))
            return (slots, aw.at[t].set(w.astype(jnp.int32)),
                    fin.at[t].set(finish), done.at[t].set(True),
                    prio.at[t].set(jnp.float32(T) - r.astype(jnp.float32)))

        _, aw, _, _, prio = jax.lax.fori_loop(
            0, T, body,
            (slots0, jnp.zeros(T, jnp.int32), jnp.zeros(T, jnp.float32),
             jnp.zeros(T, bool), jnp.zeros(T, jnp.float32)))
        return aw, prio

    return schedule


def _mix32(x):
    """splitmix-style 32-bit finalizer; the pure-Python twin lives in
    ``schedulers.det._mix32`` with the SAME constants (parity-tested)."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return x


def make_bucket_random_scheduler(n_workers, cores, max_cores=None):
    """Counter-based random static scheduler: task t goes to the
    ``hash(seed, t) mod n_eligible``-th eligible worker (id order) —
    stateless, so a whole seed batch vmaps (mirrors ``random-det``).
    Priorities are the usual decreasing-estimated-b-level ranks.  Real
    tasks keep their ids under padding, so placements are pad-invariant."""
    del max_cores                    # no per-core timeline to bound
    cores_default = _resolve_cores(n_workers, cores)

    def schedule(bspec, est_dur, est_size, bandwidth, seed=jnp.int32(0),
                 cores=None):
        del est_size, bandwidth
        cores_j = _cores_arg(cores, cores_default)
        bspec = as_jax(bspec)
        T, cpus = bspec.T, bspec.cpus
        est_dur = jnp.asarray(est_dur, jnp.float32)
        seed_u = jnp.asarray(seed).astype(jnp.uint32)
        elig = cores_j[None, :] >= cpus[:, None]              # [T, W]
        n_cand = jnp.sum(elig, axis=1).astype(jnp.uint32)     # >= 1
        h = _mix32(seed_u * jnp.uint32(0x9E3779B9)
                   + jnp.arange(T, dtype=jnp.uint32) + jnp.uint32(1))
        k = (h % jnp.maximum(n_cand, 1)).astype(jnp.int32)
        cum = jnp.cumsum(elig.astype(jnp.int32), axis=1)      # [T, W]
        pick = elig & (cum == (k + 1)[:, None])
        aw = jnp.argmax(pick, axis=1).astype(jnp.int32)
        return aw, rank_priorities(bucket_blevel(bspec, est_dur))

    return schedule


_BUCKET_FACTORIES = {
    "blevel": make_bucket_blevel_scheduler,
    "tlevel": make_bucket_tlevel_scheduler,
    "mcp": make_bucket_mcp_scheduler,
    "etf": make_bucket_etf_scheduler,
    "random": make_bucket_random_scheduler,
}


def make_bucket_scheduler(n_workers, cores, name, max_cores=None):
    """Factory for the *static* bucket schedulers: returns
    ``schedule(bspec, est_durations, est_sizes, bandwidth, seed, cores)
    -> (assignment i32[T], priority f32[T])`` with the graph late-bound
    (one trace per shape bucket) and the cluster late-bound too —
    ``cores=None`` at build time plus a static ``max_cores`` bound makes
    the per-worker vector a traced argument, so one trace also serves
    every same-W cluster.  Raises for dynamic entries (``greedy`` has no
    one-shot schedule)."""
    if name not in _BUCKET_FACTORIES:
        raise KeyError(
            f"no static vectorized scheduler {name!r} "
            f"(have {sorted(_BUCKET_FACTORIES)}; "
            f"dynamic: {sorted(k for k, v in VEC_SCHEDULERS.items() if v == 'dynamic')})")
    return _BUCKET_FACTORIES[name](n_workers, cores, max_cores)


def make_vec_scheduler(spec, n_workers, cores, name):
    """Deprecated per-graph factory — use
    ``repro.core.vectorized.api.build(spec, scheduler=name)``
    (DESIGN.md §8).  Binds ``spec`` now and returns
    ``schedule(est_durations, est_sizes, bandwidth, seed) ->
    (assignment i32[T], priority f32[T])``."""
    import warnings
    warnings.warn(
        "make_vec_scheduler is deprecated; use "
        "repro.core.vectorized.api.build(spec, scheduler=...) "
        "(DESIGN.md §8)", DeprecationWarning, stacklevel=2)
    b = as_bucketed(spec)
    fn = make_bucket_scheduler(n_workers, cores, name)
    return lambda est_dur, est_size, bandwidth, seed=jnp.int32(0): \
        fn(b, est_dur, est_size, bandwidth, seed)


def frontier_mask(frontier, n):
    """Expand a bounded frontier (``i32[C]``, ``-1`` = empty slot) into
    a dense ``bool[n]`` membership mask — the bridge between the
    simulator's carried candidate lists (DESIGN.md §3) and mask-shaped
    consumers like the schedulers."""
    return (jnp.zeros(n, bool)
            .at[jnp.clip(frontier, 0)].max(frontier >= 0))


def bucket_ready_tasks(bspec, t_done=None, t_started=None, frontier=None):
    """Mask-aware ready set: valid tasks whose produced-input count
    meets ``n_inputs`` (and that haven't started, when ``t_started`` is
    given).  Fed a ``frontier`` (the simulator's carried ``i32[CT]``
    enabled list), the O(E) edge scatter collapses to expanding the
    bounded list; otherwise it is recomputed from ``t_done``."""
    bspec = as_jax(bspec)
    if frontier is not None:
        ready = frontier_mask(frontier, bspec.T)
    else:
        if t_done is None:
            raise ValueError("bucket_ready_tasks needs t_done when no "
                             "frontier is given")
        prod_e = (t_done[bspec.producer[bspec.edge_obj]]
                  & bspec.edge_valid)
        cnt = (jnp.zeros(bspec.T, jnp.int32)
               .at[bspec.edge_task].add(prod_e.astype(jnp.int32)))
        ready = cnt >= bspec.n_inputs
    if t_started is not None:
        ready = ready & ~t_started
    return ready & bspec.task_valid


def _bind(bucket_factory):
    def make(spec, n_workers, cores):
        b = as_bucketed(spec)
        fn = bucket_factory(n_workers, cores)
        return lambda est_dur, est_size, bandwidth, seed=jnp.int32(0): \
            fn(b, est_dur, est_size, bandwidth, seed)
    return make


make_static_blevel_scheduler = _bind(make_bucket_blevel_scheduler)
make_static_tlevel_scheduler = _bind(make_bucket_tlevel_scheduler)
make_static_mcp_scheduler = _bind(make_bucket_mcp_scheduler)
make_etf_scheduler = _bind(make_bucket_etf_scheduler)
make_random_scheduler = _bind(make_bucket_random_scheduler)


def bucket_transfer_costs(bspec, size_now, missing_ow):
    """``costs(size_now, missing_ow) -> f32[T, W]``: estimated bytes to
    move so task t could run on worker w (``SimView.transfer_cost`` as
    one segment-sum).  ``missing_ow``: bool[O, W], object neither present
    at nor downloading to the worker.  Invalid edges contribute nothing
    (their index-0 link targets alias real objects)."""
    bspec = as_jax(bspec)
    T = bspec.T
    e_task, e_obj, edge_valid = bspec.edge_task, bspec.edge_obj, \
        bspec.edge_valid
    contrib = jnp.where(edge_valid[:, None],
                        size_now[e_obj][:, None] * missing_ow[e_obj],
                        0.0)                                        # [E, W]
    W = missing_ow.shape[-1]
    return jnp.zeros((T, W), jnp.float32).at[e_task].add(contrib)


def make_transfer_costs(spec, n_workers):
    """Legacy binding of ``bucket_transfer_costs`` for one graph."""
    del n_workers
    b = as_bucketed(spec)
    return lambda size_now, missing_ow: \
        bucket_transfer_costs(b, size_now, missing_ow)


def make_bucket_greedy_placer(n_workers, cores):
    """Returns ``place(bspec, ready_unassigned, cost_tw, load0, cores) ->
    i32[T]`` (proposed worker per task, -1 where none).

    Tasks are processed in id order (the order ready events are collected
    in the reference simulator); each goes to the worker minimising
    (transfer cost, queued load, worker id), and placing a task bumps the
    load its successors see — the same sequential rule as
    ``GreedyWorkerScheduler.schedule``.  Padded tasks are never ready, so
    they place nothing and bump no loads.  ``cores`` is traced like the
    bucket schedulers' (``None`` falls back to the build-time cluster).

    The loop makes one trip per task to place, not one per task of the
    bucket: an unplaced task changes nothing, so skipping it is exact,
    and an invocation that places nothing costs no trip.  Under ``vmap``
    the batch runs as many trips as its busiest lane.
    """
    cores_default = _resolve_cores(n_workers, cores)
    BIG = jnp.int32(np.iinfo(np.int32).max)

    def place(bspec, ready_unassigned, cost_tw, load0, cores=None):
        cores_j = _cores_arg(cores, cores_default)
        bspec = as_jax(bspec)
        cpus = bspec.cpus

        def cond(st):
            _, _, waiting = st
            return jnp.any(waiting)

        def body(st):
            pw, load, waiting = st
            t = jnp.argmax(waiting)                # smallest id left
            c = jnp.where(cores_j >= cpus[t], cost_tw[t], jnp.inf)
            # ineligible workers are inf/BIG-masked just above; the mins
            # pick among eligible candidates only
            cand = c == jnp.min(c)  # simlint: disable=PY205
            ld = jnp.where(cand, load, BIG)
            cand = cand & (ld == jnp.min(ld))  # simlint: disable=PY205
            w = jnp.argmax(cand).astype(jnp.int32)  # first = smallest id
            return (pw.at[t].set(w), load.at[w].add(1),
                    waiting.at[t].set(False))

        pw, _, _ = jax.lax.while_loop(
            cond, body,
            (jnp.full(bspec.T, -1, jnp.int32), load0, ready_unassigned))
        return pw

    return place


def make_greedy_placer(spec, n_workers, cores):
    """Legacy binding of ``make_bucket_greedy_placer`` for one graph."""
    b = as_bucketed(spec)
    fn = make_bucket_greedy_placer(n_workers, cores)
    return lambda ready_unassigned, cost_tw, load0: \
        fn(b, ready_unassigned, cost_tw, load0)
