"""Vectorized, fixed-shape discrete-event simulator (TPU-native ESTEE).

Executes task graphs on a simulated cluster under the max-min or simple
network model, entirely inside ``jax.lax.while_loop`` over dense arrays —
so whole batches of simulations (GA populations, bandwidth/msd/imode
sweeps, seeds, and — via shape buckets — whole *graph sets*) run in
parallel under ``jax.vmap`` / ``pjit``.

Two semantics, each in two bindings (scoping in DESIGN.md §3):

* ``make_bucket_simulator`` / ``make_simulator`` — a *static* schedule
  (``task -> worker`` + priorities) supplied by the caller, msd=0,
  decision_delay=0;
* ``make_bucket_dynamic_simulator`` / ``make_dynamic_simulator`` — the
  paper's dynamic-scheduling machinery: MSD-gated scheduler invocations
  with event batching, a ``decision_delay`` before assignments reach the
  workers, and imode-filtered estimates (dense arrays from
  ``imodes.encode_imode``, switching to true values for finished
  elements), with an in-loop vectorized scheduler
  (``vectorized.scheduling``).

The ``make_bucket_*`` forms take the graph as a runtime
``BucketedGraphSpec`` argument (``vectorized.specs``): one jit trace
serves every graph padded into the same shape bucket, and a stacked
bucket batch rides a single ``vmap`` axis (``BucketedGridRunner``).
The legacy forms bind one unpadded ``GraphSpec`` at build time.

Mask semantics (padding is inert): invalid tasks are born
started+finished with ``t_finish`` excluded from the makespan; invalid
edges never satisfy inputs, never carry flows, never claim a
(object, destination) dedup key and never contribute download priority;
invalid objects have zero size.  The cluster is a per-worker
``cores: i32[W]`` vector — heterogeneous shapes (``1x8+4x2``) and
zero-core padded workers ride the same code path as homogeneous ones —
and may be *late-bound*: build with ``cores=None`` + a static
``max_cores`` bound and pass the vector at call time (traced), so one
compiled program serves every same-W cluster signature and
``BucketedGridRunner`` stacks a whole cluster group on a vmap axis.

Shared semantics mirror the reference simulator (``core.simulator``):

* downloads come from the producing worker, deduplicated per
  (object, destination); slot limits ``DOWNLOAD_SLOTS``/worker +
  ``PAIR_SLOTS``/source pair (max-min model) or unlimited (simple
  model); priorities boosted for ready tasks;
* the Appendix-A task start rule incl. the priority/blocking guard;
* max-min progressive filling recomputed at every event — over the
  bounded *flow-slot pool* (``S = DOWNLOAD_SLOTS * W`` in-flight
  flows, DESIGN.md §3) rather than all E edges, with the solver routed
  through ``kernels.ops.waterfill`` (Pallas MXU kernel on TPU, jnp
  progressive filling elsewhere; ``waterfill_impl``).  The per-edge
  path survives as ``flow_slots=False``, the near-bitwise parity
  baseline (``tests/test_flowslots.py``).

The static/list scheduler family (``blevel``/``tlevel``/``mcp``/``etf``/
``random``) and the dynamic ``greedy`` run in-loop; rescheduling work
stealing (``ws``), the in-loop genetic scheduler and the RNG-tie-break
stochastic variants stay on the reference simulator — documented scoping
in DESIGN.md §3.
"""
from __future__ import annotations

import typing
import warnings

import numpy as np
import jax
import jax.numpy as jnp

from .specs import (GraphSpec, encode_graph, as_bucketed, as_jax,
                    bucket_shape, frontier_caps_for, pad_spec, pad_to,
                    stack_specs)
from .waterfill import waterfill
from .scheduling import (bucket_blevel, bucket_transfer_costs,
                         make_bucket_greedy_placer, make_bucket_scheduler,
                         rank_priorities, VEC_SCHEDULERS, _resolve_cores)

READY_BOOST = 1_000_000.0
TIME_EPS = 1e-6
BYTES_EPS = 1e-3
NEG = jnp.float32(-3e38)
NEG_TIME = jnp.float32(-1e30)

# Appendix-A download-slot limits (shared with the reference worker):
# at most DOWNLOAD_SLOTS concurrent downloads per destination worker and
# PAIR_SLOTS per (source, destination) pair under the max-min model.
# They also bound the *flow-slot pool*: at any instant at most
# S = DOWNLOAD_SLOTS * W flows are in flight, so the waterfill, rate
# integration and next-event reduction run over [S] instead of [E].
DOWNLOAD_SLOTS = 4
PAIR_SLOTS = 2

# The dynamic event loop's phases, as ``jax.named_scope`` names: the
# compiler keeps them in every HLO instruction's ``op_name``, so a device
# trace can charge each op to its phase.  ``sim.schedule``: applying due
# assignments, scheduler invocations and the static schedule before the
# loop; ``sim.ready``: detecting ready flows and tasks and starting them;
# ``sim.rates``: the network model's flow rates (the max-min waterfill);
# ``sim.advance``: the next event time, progress and completions;
# ``sim.backlog``, inside ``sim.ready``: the wanted downloads that found
# the flow frontier full — their exact pick and their refill.
SIM_PHASES = ("sim.schedule", "sim.ready", "sim.rates", "sim.advance",
              "sim.backlog")
SCHEDULE, READY, RATES, ADVANCE, BACKLOG = SIM_PHASES


class SimResult(typing.NamedTuple):
    """Uniform result of every simulator path (static, dynamic,
    bucketed) — a pytree, so it vmaps/jits like the old tuples.

    ``makespan`` is NaN whenever ``ok`` is False.  ``overflow`` is the
    honest-failure flag of the bounded carries (flow-slot pool or task
    frontier, DESIGN.md §3): capacity was exceeded, results are invalid,
    and ``ok`` is already poisoned — widen ``frontier_caps`` or fall
    back to ``frontier=False``.  The flow frontier never overflows: a
    wanted download that finds it full waits in its backlog, and
    ``backlog_peak`` is the most (object, destination) keys that waited
    there at any step (0 where the frontier held them all).
    ``n_events`` counts processed completions (tasks + flows);
    ``n_steps`` counts ``while_loop`` iterations.  Same-timestamp
    completions are batched into one step, so ``n_events / n_steps`` is
    the measured event-batching factor."""
    makespan: jnp.ndarray      # f32
    transferred: jnp.ndarray   # f32 — bytes moved across workers
    ok: jnp.ndarray            # bool
    overflow: jnp.ndarray      # bool
    n_events: jnp.ndarray      # i32
    n_steps: jnp.ndarray       # i32
    backlog_peak: jnp.ndarray  # i32


def _frontier_append(fr, new_mask, ids):
    """Append ``ids[new_mask]`` into the free (``-1``) slots of the
    bounded frontier ``fr``; returns ``(fr, n_left)``.

    Candidates fill free slots in index order, both sides ranked by
    cumsum.  Formulated as a *gather*: each free slot binary-searches
    the candidates' running count for its own rank (a full-width
    scatter here costs ~40us of fixed XLA:CPU overhead per event —
    this is a couple of vector ops plus log(N) gathers).
    ``n_left`` (i32) counts the candidates that outnumbered the free
    slots: the task frontier folds ``n_left > 0`` into ``ok`` so a
    too-small derived capacity fails loudly instead of silently
    dropping work, the flow frontier keeps them in its backlog."""
    if fr.shape[0] == 0 or ids.shape[0] == 0:       # degenerate axis
        return fr, jnp.sum(new_mask, dtype=jnp.int32)
    free = fr < 0
    free_rank = jnp.cumsum(free.astype(jnp.int32))          # 1-based
    cs = jnp.cumsum(new_mask.astype(jnp.int32))             # 1-based
    total_new = cs[-1]
    # first candidate index whose running count reaches the slot's rank
    # == the rank-th new candidate (cs jumps to that rank at its index)
    src = jnp.searchsorted(cs, free_rank, side="left")
    take = free & (free_rank <= total_new)
    src_c = jnp.clip(src, 0, ids.shape[0] - 1)
    fr = jnp.where(take, ids[src_c].astype(jnp.int32), fr)
    return fr, jnp.maximum(total_new - free_rank[-1], 0)


def _members(fr, n):
    """``bool[n]``: the ids the frontier ``fr`` holds."""
    return jnp.zeros(n, bool).at[jnp.clip(fr, 0)].max(fr >= 0)


def _run_once(pred, fn, carry):
    """``fn(carry)`` where ``pred`` holds, else ``carry``: a
    ``while_loop`` of at most one trip.  Under ``vmap`` a ``lax.cond``
    with a batched predicate becomes a select that runs ``fn`` for every
    lane in every iteration; a batched ``while_loop`` runs its body only
    while some lane needs it, so where none does this costs the
    predicate."""
    def body(c):
        return jnp.bool_(False), fn(c[1])
    return jax.lax.while_loop(lambda c: c[0], body, (pred, carry))[1]


def _flow_pick_rounds(st, alive, c_dst, c_src, c_prio, c_bytes, ids, *,
                      flow_rounds, counts=None):
    """The Appendix-A download rounds over one axis of candidate flows
    (a flow frontier's entries, or every edge where its backlog is not
    empty).  Each round, every destination worker under
    ``DOWNLOAD_SLOTS`` downloads starts its best eligible candidate —
    the highest ``c_prio``, then the smallest edge id (``ids``) — whose
    (source, destination) pair is under ``PAIR_SLOTS``: the reference's
    ``_start_downloads`` over that worker's wanted keys.  ``counts`` is
    ``(dcnt[W], c_pcnt[C])`` where no slot pool holds the downloads in
    flight; with the pool they are read off it and each pick moves into
    it.  Returns ``(st, picked)``."""
    if counts is None:
        occ = st["slot_edge"] >= 0
        W = occ.shape[0] // DOWNLOAD_SLOTS
        dcnt = occ.reshape(W, DOWNLOAD_SLOTS).sum(axis=1, dtype=jnp.int32)
        c_pair = c_src * W + c_dst
        # each candidate's pair occupancy, counted once on the candidate
        # axis against the slots' pairs
        pair_s = (st["slot_src"] * W
                  + np.arange(W * DOWNLOAD_SLOTS, dtype=np.int32)
                  // DOWNLOAD_SLOTS)
        c_pcnt = jnp.sum((c_pair[:, None] == pair_s[None, :])
                         & occ[None, :], axis=1, dtype=jnp.int32)
    else:
        dcnt, c_pcnt = counts
        W = dcnt.shape[0]
        c_pair = c_src * W + c_dst
    neg_id = -ids.astype(jnp.float32)
    onehot_w = _onehot(c_dst, W)
    alive0 = alive
    for _ in range(flow_rounds):
        eligible = (alive
                    & (_bucket_read(onehot_w, dcnt) < DOWNLOAD_SLOTS)
                    & (c_pcnt < PAIR_SLOTS))
        pick = _pick_per_bucket(onehot_w, eligible, c_prio, neg_id)
        if counts is None:
            st = _acquire_slots(st, pick, onehot_w, c_src, c_bytes, ids=ids)
        # occupancy moves only by this event's own picks (completions
        # happen at the end of the body); the picks compact to one pair
        # per worker, so the count deltas are [C, W] dense reduces, not
        # scatters or gathers
        pw_pair = jnp.max(jnp.where(onehot_w & pick[:, None],
                                    c_pair[:, None], -1), axis=0,
                          initial=-1)
        picked_w = pw_pair >= 0
        dcnt = dcnt + picked_w.astype(jnp.int32)
        c_pcnt = c_pcnt + jnp.sum((c_pair[:, None] == pw_pair[None, :])
                                  & picked_w[None, :], axis=1,
                                  dtype=jnp.int32)
        alive = alive & ~pick
    return st, alive0 & ~alive


def _backlog_rounds(st, fr, waiting, c_dst, c_src, c_prio, c_bytes, *,
                    flow_rounds, counts=None):
    """The exact pick while wanted keys wait outside the flow frontier
    ``fr``: ``_flow_pick_rounds`` over every edge, with the frontier's
    entries and the ``waiting`` key representatives (``bool[E]``) as
    candidates — what the rounds over a frontier that held them all
    would pick.  Returns ``(st, fr less its picked entries, members,
    picked)``: ``members`` marks the frontier's entries before the
    picks."""
    E = waiting.shape[0]
    members = _members(fr, E)
    st, picked = _flow_pick_rounds(
        st, members | waiting, c_dst, c_src, c_prio, c_bytes,
        jnp.arange(E, dtype=jnp.int32), flow_rounds=flow_rounds,
        counts=counts)
    fr = jnp.where((fr >= 0) & picked[jnp.clip(fr, 0)], -1, fr)
    return st, fr, members, picked


def _resolve_frontier(frontier, *, simple: bool, use_slots: bool,
                      dynamic: bool) -> bool:
    """The ``frontier`` kwarg tri-state: ``None`` defaults on wherever
    supported, mirroring the ``flow_slots`` rollout.  The dynamic
    max-min frontier derives in-flight state from the slot pool, so it
    requires ``flow_slots``; asking for both explicitly is an error,
    while the default quietly stays on the per-edge baseline."""
    if frontier is False:
        return False
    if dynamic and not simple and not use_slots:
        if frontier is True:
            raise ValueError(
                "frontier=True requires flow_slots on the dynamic max-min "
                "path (in-flight flow state is derived from the slot "
                "pool); drop flow_slots=False or pass frontier=False")
        return False
    return True


def _resolve_waterfill_impl(waterfill_impl: str) -> str:
    if waterfill_impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if waterfill_impl not in ("jnp", "pallas"):
        raise ValueError(f"waterfill_impl must be 'auto'|'jnp'|'pallas', "
                         f"got {waterfill_impl!r}")
    return waterfill_impl


def _make_waterfill(waterfill_impl: str):
    """The per-simulation max-min rate solver: ``wf(src, dst, active,
    caps) -> rates``.  ``"jnp"`` is the progressive-filling while_loop
    (``vectorized.waterfill`` — CPU and fallback path); ``"pallas"``
    routes through ``kernels.ops.waterfill`` so the one-hot/MXU Pallas
    kernel compiles natively (TPU only) with the vmap batch as the
    Pallas grid.  ``"auto"`` picks per backend."""
    if _resolve_waterfill_impl(waterfill_impl) == "pallas":
        from ...kernels.ops import waterfill as kernel_waterfill

        def wf(src, dst, active, caps):
            return kernel_waterfill(src, dst, active, caps, caps,
                                    use_pallas=True)
        return wf
    return lambda src, dst, active, caps: waterfill(src, dst, active,
                                                    caps, caps)


def _acquire_slots(st, pick, onehot, src_e, bytes_e, ids=None):
    """Move this round's picked flows (<= 1 per destination worker —
    ``_pick_per_bucket``'s contract) into the flow-slot pool: each
    destination worker owns ``DOWNLOAD_SLOTS`` consecutive slots, and a
    picked flow takes the first free one.  Eligibility already enforced
    occupancy < DOWNLOAD_SLOTS, so a free slot must exist; ``overflow``
    records any violation of that invariant and poisons ``ok``.

    ``onehot`` is the ``[F, W]`` destination one-hot (``_onehot``) of
    the pick axis, which may be per-edge ``[E]`` or per-frontier-
    candidate ``[CF]``; in the latter case ``ids`` supplies the real
    edge id per candidate (``slot_edge`` always stores edge ids,
    whatever the pick axis)."""
    W = onehot.shape[1]
    if ids is None:
        ids = jnp.arange(pick.shape[0], dtype=jnp.int32)
    # each worker's (single) pick, read out by dense masked reduces —
    # neither a scatter (see _bucket_max) nor a gather (_bucket_read):
    # a column of ``sel`` holds at most one True, so its masked sum adds
    # one value to zeros, exact for ints and floats alike
    sel = onehot & pick[:, None]
    picked_w = jnp.any(sel, axis=0)
    occ_w = (st["slot_edge"] >= 0).reshape(W, DOWNLOAD_SLOTS)
    first_free = jnp.argmin(occ_w.astype(jnp.int32), axis=1)
    has_free = ~jnp.all(occ_w, axis=1)
    take = picked_w & has_free
    # dense slot write: slot (w, first_free[w]) takes worker w's pick
    put = ((jnp.arange(DOWNLOAD_SLOTS)[None, :] == first_free[:, None])
           & take[:, None]).reshape(-1)
    def spread(v):
        v_w = jnp.sum(jnp.where(sel, v[:, None], 0), axis=0, dtype=v.dtype)
        return jnp.broadcast_to(v_w[:, None],
                                (W, DOWNLOAD_SLOTS)).reshape(-1)
    return dict(
        st,
        slot_edge=jnp.where(put, spread(ids), st["slot_edge"]),
        slot_src=jnp.where(put, spread(src_e), st["slot_src"]),
        slot_rem=jnp.where(put, spread(bytes_e), st["slot_rem"]),
        overflow=st["overflow"] | jnp.any(picked_w & ~has_free),
    )

# jit-trace odometer: every trace of a simulator ``run`` body bumps it
# (tracing happens exactly once per XLA compilation; eager calls are
# filtered out via ``trace_state_clean``), so callers can assert
# compile counts — the survey runner's one-compile-per-bucket
# regression gate reads it through ``trace_counter``.
_TRACE_COUNT = [0]


def _count_trace():
    # trace_state_clean left jax.core after the 0.4 line; if the probe
    # is unavailable, count every call (the pre-guard behavior: correct
    # under jit, over-counts only eager/bare-vmap use)
    probe = getattr(jax.core, "trace_state_clean", None)
    if probe is None or not probe():
        _TRACE_COUNT[0] += 1


class trace_counter:
    """Scoped compile counting: ``with trace_counter() as tc: ...;
    tc.count`` is the number of simulator jit traces inside the block
    (valid during and after the block).  Nests safely — it reads
    deltas, never resets the global odometer."""

    def __enter__(self):
        self._start = _TRACE_COUNT[0]
        return self

    def __exit__(self, *exc):
        return False

    @property
    def count(self) -> int:
        return _TRACE_COUNT[0] - self._start


def make_bucket_simulator(n_workers: int, cores, netmodel: str = "maxmin",
                          flow_rounds: int = 4, max_steps: int | None = None, *,
                          max_cores: int | None = None, flow_slots=None,
                          frontier=None, frontier_caps=None,
                          waterfill_impl: str = "auto"):
    """Returns ``run(bspec, assignment, priority, durations, sizes,
    bandwidth, cores) -> SimResult`` — a pure JAX function with the
    graph late-bound as a ``BucketedGraphSpec``.  Thin-wrapper note:
    prefer the ``repro.core.vectorized.api.build`` front door; the full
    argument contract lives in DESIGN.md §8 and the carry invariants in
    DESIGN.md §3.

    ``frontier`` (default on; ``False`` = the retained per-edge-scan
    baseline, the parity reference) compacts per-event eligibility onto
    bounded ready frontiers carried in the loop: candidate flows
    (``i32[CF]``) and enabled-not-started tasks (``i32[CT]``), with
    capacities derived per bucket by ``specs.frontier_caps_for`` or
    overridden via ``frontier_caps=(CF, CT)``.  The flow/task pick
    rounds then touch O(frontier) entries instead of O(E)/O(T), and
    with ``flow_slots`` the loop carries no per-edge state at all.  The
    flow cap sizes a window, not a limit: a candidate flow that finds
    the list full waits in a backlog of (object, destination) keys,
    and while any waits the pick runs exactly over every edge
    (``_backlog_rounds``; ``SimResult.backlog_peak``).  A task frontier
    overflow poisons ``ok`` (``SimResult.overflow`` — honest failure,
    never silent truncation).

    ``flow_slots=False`` keeps the legacy per-edge ``f32[E]`` network
    state; ``waterfill_impl`` routes the max-min solver (``"jnp"`` |
    ``"pallas"`` | ``"auto"``); ``cores=None`` + ``max_cores`` makes
    the cluster a traced call-time argument.
    """
    W = n_workers
    cores_default = _resolve_cores(n_workers, cores)
    if max_cores is None:
        if cores_default is None:
            raise ValueError("max_cores is required when cores is None")
        max_cores = max(int(cores_default.max()), 1)
    max_cores = max(int(max_cores), 1)
    simple = netmodel == "simple"
    use_slots_cfg = (flow_slots is not False) and not simple
    use_frontier = _resolve_frontier(frontier, simple=simple,
                                     use_slots=use_slots_cfg, dynamic=False)
    wf = None if simple else _make_waterfill(waterfill_impl)
    S = W * DOWNLOAD_SLOTS
    slot_dst = jnp.arange(S, dtype=jnp.int32) // DOWNLOAD_SLOTS

    def run(bspec, assignment, priority, durations=None, sizes=None,
            bandwidth=jnp.float32(100 * 1024 * 1024), cores=None):
        _count_trace()
        bspec = as_jax(bspec)
        T, O, E = bspec.T, bspec.O, bspec.E
        steps_cap = max_steps if max_steps is not None else 4 * (T + E) + 64
        e_task, e_obj = bspec.edge_task, bspec.edge_obj
        producer, n_inputs, cpus = bspec.producer, bspec.n_inputs, bspec.cpus
        task_valid, edge_valid = bspec.task_valid, bspec.edge_valid
        durations = jnp.asarray(bspec.durations if durations is None
                                else durations, jnp.float32)
        sizes = jnp.asarray(bspec.sizes if sizes is None else sizes,
                            jnp.float32)
        bandwidth = jnp.asarray(bandwidth, jnp.float32)
        if cores is None:
            if cores_default is None:
                raise ValueError("simulator built without a cluster: pass "
                                 "cores at call time")
            cores = cores_default
        cores_j = jnp.asarray(cores, jnp.int32)
        assignment = jnp.clip(jnp.asarray(assignment, jnp.int32), 0, W - 1)
        priority = jnp.asarray(priority, jnp.float32)
        use_slots = use_slots_cfg and E > 0

        obj_worker = assignment[producer]          # where each obj is born
        f_dst = assignment[e_task]                 # flow = edge
        f_src = obj_worker[e_obj]
        prod_task_e = producer[e_obj]              # producing task per edge
        prio_e = priority[e_task]                  # static: hoisted gathers
        cross = (f_src != f_dst) & edge_valid
        # dedup: one flow per (obj, dst); rep = smallest valid edge idx
        # in bucket (invalid edges alias key (0, dst) — masked out here)
        key = e_obj * W + f_dst
        big = jnp.full(O * W, E, jnp.int32)
        e_ids = jnp.arange(E, dtype=jnp.int32)
        rep_per_key = big.at[key].min(jnp.where(edge_valid, e_ids, E))
        rep = rep_per_key[key]                     # i32[E]
        is_rep = (rep == e_ids) & edge_valid
        needed = cross & is_rep
        f_bytes = jnp.where(edge_valid, sizes[e_obj], 0.0)
        pair = f_src * W + f_dst
        # loop-invariant worker one-hots of the tasks and the flows: the
        # pick rounds read per-worker tables through them, and
        # body_frontier's core release reduces over the tasks' one
        onehot_aw = _onehot(assignment, W)
        onehot_f = _onehot(f_dst, W)
        if frontier_caps is None:
            CF, CT = frontier_caps_for((T, O, E), n_workers=W)
        else:
            # an explicit override never exceeds the axis itself
            CF, CT = min(frontier_caps[0], E), min(frontier_caps[1], T)
        # the flow list holds distinct reps, so one of E entries can
        # hold every key at once: only a narrower one keeps a backlog
        windowed = use_frontier and not simple and CF < E
        t_ids = jnp.arange(T, dtype=jnp.int32)

        state0 = dict(
            now=jnp.float32(0.0),
            t_started=~task_valid,
            t_done=~task_valid,
            t_finish=jnp.full(T, jnp.inf, jnp.float32),
            free=cores_j.astype(jnp.int32),
            steps=jnp.int32(0),
            n_events=jnp.int32(0),
        )
        if not (use_frontier and use_slots):
            # frontier + slots is the no-per-edge-carry mode: flow
            # identity lives in the slot pool, satisfaction in sat_cnt
            state0.update(f_started=jnp.zeros(E, bool),
                          f_done=jnp.zeros(E, bool))
        if use_slots:
            # in-flight flow state lives in the compact slot pool; the
            # per-edge f32[E] remaining-bytes carry disappears entirely
            state0.update(
                slot_edge=jnp.full(S, -1, jnp.int32),
                slot_src=jnp.zeros(S, jnp.int32),
                slot_rem=jnp.zeros(S, jnp.float32),
                overflow=jnp.bool_(False),
            )
        else:
            state0["f_rem"] = f_bytes
        if use_frontier:
            state0.setdefault("overflow", jnp.bool_(False))
            fr_task0, left0 = _frontier_append(jnp.full(CT, -1, jnp.int32),
                                               (n_inputs <= 0) & task_valid,
                                               t_ids)
            state0.update(sat_cnt=jnp.zeros(T, jnp.int32), fr_task=fr_task0,
                          overflow=state0["overflow"] | (left0 > 0))
            if not simple:
                state0.update(in_cnt=jnp.zeros(T, jnp.int32),
                              fr_flow=jnp.full(CF, -1, jnp.int32))
            if windowed:
                # the flow frontier's backlog: keys that found it full,
                # their count, and the most that ever waited at once
                state0.update(key_wait=jnp.zeros(O * W, bool),
                              n_wait=jnp.int32(0),
                              backlog_peak=jnp.int32(0))
            if use_slots:
                state0["transferred"] = jnp.float32(0.0)

        def edge_satisfied(st):
            """input edge e is satisfied at the consumer's worker."""
            prod_done = st["t_done"][prod_task_e]
            local = prod_done & ~cross & edge_valid
            moved = st["f_done"][rep] & cross
            return local | moved

        def start_flows(st):
            produced = st["t_done"][prod_task_e]
            cnt = jnp.zeros(T, jnp.int32).at[e_task].add(
                (produced & edge_valid).astype(jnp.int32))
            ready_boost = (cnt >= n_inputs)[e_task].astype(jnp.float32)
            # download priority = max over same (obj,dst) edges
            raw = jnp.where(edge_valid, prio_e + READY_BOOST * ready_boost,
                            NEG)
            mx = jnp.full(O * W, NEG, jnp.float32).at[key].max(raw)
            f_prio = mx[key]
            if simple:
                eligible = needed & ~st["f_started"] & produced
                st = dict(st, f_started=st["f_started"] | eligible)
                return st
            # round-invariant eligibility base; only the slot-limit
            # masks and this event's own picks change per round
            base = needed & ~st["f_started"] & produced
            for _ in range(flow_rounds):
                if use_slots:
                    # slot occupancy *is* the Appendix-A accounting
                    occ = st["slot_edge"] >= 0
                    dcnt = (occ.reshape(W, DOWNLOAD_SLOTS)
                            .sum(axis=1, dtype=jnp.int32))
                    pair_s = st["slot_src"] * W + slot_dst
                    pcnt = (jnp.zeros(W * W, jnp.int32)
                            .at[pair_s].add(occ.astype(jnp.int32)))
                else:
                    active = st["f_started"] & ~st["f_done"]
                    af = active.astype(jnp.int32)
                    dcnt = jnp.zeros(W, jnp.int32).at[f_dst].add(af * needed)
                    pcnt = (jnp.zeros(W * W, jnp.int32)
                            .at[pair].add(af * needed))
                eligible = (base
                            & (_bucket_read(onehot_f, dcnt) < DOWNLOAD_SLOTS)
                            & (pcnt[pair] < PAIR_SLOTS))
                pick = _pick_per_bucket(onehot_f, eligible, f_prio)
                base = base & ~pick
                st = dict(st, f_started=st["f_started"] | pick)
                if use_slots:
                    st = _acquire_slots(st, pick, onehot_f, f_src, f_bytes)
            return st

        def start_tasks(st):
            sat = edge_satisfied(st).astype(jnp.int32)
            cnt = jnp.zeros(T, jnp.int32).at[e_task].add(sat)
            enabled = (cnt >= n_inputs) & ~st["t_started"]
            for _ in range(max_cores):
                free_at = _bucket_read(onehot_aw, st["free"])
                waiting = enabled & ~st["t_started"]
                blocked = waiting & (cpus > free_at)
                maxblk = jnp.full(W, NEG, jnp.float32).at[assignment].max(
                    jnp.where(blocked, priority, NEG))
                cand = (waiting & (cpus <= free_at)
                        & (priority >= _bucket_read(onehot_aw, maxblk)))
                pick = _pick_per_bucket(onehot_aw, cand, priority)
                st = dict(
                    st,
                    t_started=st["t_started"] | pick,
                    t_finish=jnp.where(pick, st["now"] + durations,
                                       st["t_finish"]),
                    free=st["free"] - jnp.zeros(W, jnp.int32)
                    .at[assignment].add(jnp.where(pick, cpus, 0)),
                )
            return st

        def start_flows_frontier(st):
            """Max-min flow picks over the bounded candidate list.  The
            download priority stays *exact*: one O(E) scatter-max into
            the (obj, dst) key space per event, gathered only at the CF
            candidates — the key max ranges over all same-key edges,
            frontier members or not, exactly like the baseline.  While
            keys wait in the backlog, the pick runs over every edge
            instead (``_backlog_rounds``), and the keys it leaves
            waiting refill the frontier's free entries."""
            ready_t = st["in_cnt"] >= n_inputs
            raw = jnp.where(edge_valid,
                            prio_e + READY_BOOST
                            * ready_t[e_task].astype(jnp.float32), NEG)
            keymax = jnp.full(O * W, NEG, jnp.float32).at[key].max(raw)
            fr = st["fr_flow"]
            cid = jnp.clip(fr, 0)
            c_dst = f_dst[cid]
            c_src = f_src[cid]
            alive = fr >= 0
            if windowed:
                waiting = st["n_wait"] > 0
                alive = alive & ~waiting
            counts = None
            if not use_slots:
                af = (st["f_started"] & ~st["f_done"]).astype(jnp.int32)
                dcnt = jnp.zeros(W, jnp.int32).at[f_dst].add(af * needed)
                pcnt = jnp.zeros(W * W, jnp.int32).at[pair].add(af * needed)
                counts = (dcnt, pcnt[c_src * W + c_dst])
            # the baseline breaks priority ties by smallest edge id;
            # frontier slot order is arrival order, so the id rides
            # along as an explicit key
            st, picked = _flow_pick_rounds(
                st, alive, c_dst, c_src, keymax[key[cid]], f_bytes[cid], fr,
                flow_rounds=flow_rounds, counts=counts)
            if not use_slots:
                # one deferred scatter for all rounds' starts
                st = dict(st, f_started=st["f_started"].at[
                    jnp.where(picked, fr, E)].set(True, mode="drop"))
            st = dict(st, fr_flow=jnp.where(picked, -1, fr))
            if not windowed:
                return st

            def drain(sub):
                with jax.named_scope(BACKLOG):
                    wait_e = needed & sub["key_wait"][key]
                    sub, fr, _, picked = _backlog_rounds(
                        sub, sub["fr_flow"], wait_e, f_dst, f_src,
                        keymax[key], f_bytes, flow_rounds=flow_rounds,
                        counts=None if use_slots else (dcnt, pcnt[pair]))
                    if not use_slots:
                        sub = dict(sub, f_started=sub["f_started"] | picked)
                    left = wait_e & ~picked
                    fr, n_left = _frontier_append(fr, left, e_ids)
                    key_wait = jnp.zeros(O * W, bool).at[key].max(
                        left & ~_members(fr, E))
                    return dict(sub, fr_flow=fr, key_wait=key_wait,
                                n_wait=n_left)

            moved = ("fr_flow", "key_wait", "n_wait",
                     *(("slot_edge", "slot_src", "slot_rem", "overflow")
                       if use_slots else ("f_started",)))
            return dict(st, **_run_once(waiting, drain,
                                        {k: st[k] for k in moved}))

        def start_tasks_frontier(st):
            """Appendix-A start rounds over the bounded enabled-task
            list — the frontier invariantly holds exactly the enabled &
            not-started tasks, so blocking/eligibility match the full
            [T] scan; ``-task_id`` reproduces the baseline tie-break."""
            fr = st["fr_task"]
            tid = jnp.clip(fr, 0)
            alive0 = fr >= 0
            alive = alive0
            c_w = assignment[tid]
            c_cpus = cpus[tid]
            c_prio = priority[tid]
            c_fin = durations[tid]
            neg_id = -fr.astype(jnp.float32)
            free = st["free"]
            onehot_w = _onehot(c_w, W)
            for _ in range(max_cores):
                free_at = _bucket_read(onehot_w, free)
                blocked = alive & (c_cpus > free_at)
                maxblk = _bucket_max(onehot_w,
                                     jnp.where(blocked, c_prio, NEG))
                cand = (alive & (c_cpus <= free_at)
                        & (c_prio >= _bucket_read(onehot_w, maxblk)))
                pick = _pick_per_bucket(onehot_w, cand, c_prio, neg_id)
                # <= 1 pick per worker, so the core delta per worker is
                # a dense masked max, not a scatter-add
                free = free - jnp.max(jnp.where(onehot_w & pick[:, None],
                                                c_cpus[:, None], 0), axis=0,
                                      initial=0)
                alive = alive & ~pick
            # time does not advance between rounds, so all rounds' starts
            # share one finish-time value and fold into one scatter each
            newly = alive0 & ~alive
            dest = jnp.where(newly, fr, T)
            return dict(st,
                        t_started=st["t_started"].at[dest].set(True,
                                                               mode="drop"),
                        t_finish=st["t_finish"].at[dest].set(
                            st["now"] + c_fin, mode="drop"),
                        free=free,
                        fr_task=jnp.where(newly, -1, fr))

        def rates_of(st):
            if simple:
                active = st["f_started"] & ~st["f_done"] & needed
                return jnp.where(active, bandwidth, 0.0)
            caps = jnp.full(W, bandwidth, jnp.float32)
            if use_slots:
                occ = st["slot_edge"] >= 0
                return wf(st["slot_src"], slot_dst, occ, caps)
            active = st["f_started"] & ~st["f_done"] & needed
            return wf(f_src, f_dst, active, caps)

        def body(st):
            st = start_flows(st)
            st = start_tasks(st)
            rates = rates_of(st)
            running = st["t_started"] & ~st["t_done"]
            t_next = jnp.min(jnp.where(running, st["t_finish"], jnp.inf))
            # f32 time resolution: ETAs below the representable step at
            # `now` are completed immediately (mirrors the reference
            # simulator's sub-byte remainder rule, scaled for f32).
            gran = st["now"] * 6e-7 + TIME_EPS
            if use_slots:
                active = st["slot_edge"] >= 0
                rem = st["slot_rem"]
            else:
                active = st["f_started"] & ~st["f_done"] & needed
                rem = st["f_rem"]
            # double-where: unselected lanes still evaluate the division,
            # so the denominator needs its own guard or rate-0 lanes
            # produce inf*0/NaN that poison min-reductions downstream
            safe_rates = jnp.where(rates > 0, rates, 1.0)
            f_eta = jnp.where(active & (rates > 0), rem / safe_rates,
                              jnp.inf)
            f_eta = jnp.where(f_eta <= gran, 0.0, f_eta)
            f_next = st["now"] + jnp.min(f_eta, initial=jnp.inf)
            nxt = jnp.minimum(t_next, f_next)
            nxt = jnp.maximum(nxt, st["now"])          # never go back
            dt = jnp.where(jnp.isfinite(nxt), nxt - st["now"], 0.0)
            now = jnp.where(jnp.isfinite(nxt), nxt, st["now"])
            rem = jnp.where(active, rem - rates * dt, rem)
            done_now = active & ((rem <= BYTES_EPS) | (rem <= rates * gran))
            t_newly = running & (st["t_finish"] <= now + TIME_EPS)
            free = st["free"] + jnp.zeros(W, jnp.int32).at[assignment].add(
                jnp.where(t_newly, cpus, 0))
            st = dict(st, now=now, t_done=st["t_done"] | t_newly, free=free,
                      steps=st["steps"] + 1,
                      n_events=st["n_events"]
                      + jnp.sum(t_newly.astype(jnp.int32))
                      + jnp.sum(done_now.astype(jnp.int32)))
            if use_slots:
                # completion flags scatter back per edge; finished slots
                # release immediately (free for next event's acquires)
                newly_done = (jnp.zeros(E, bool)
                              .at[jnp.clip(st["slot_edge"], 0)].max(done_now))
                return dict(st, slot_rem=rem,
                            slot_edge=jnp.where(done_now, -1,
                                                st["slot_edge"]),
                            f_done=st["f_done"] | newly_done)
            return dict(st, f_rem=rem, f_done=st["f_done"] | done_now)

        def body_frontier(st):
            if not simple:
                st = start_flows_frontier(st)
            st = start_tasks_frontier(st)
            rates = rates_of(st)
            running = st["t_started"] & ~st["t_done"]
            t_next = jnp.min(jnp.where(running, st["t_finish"], jnp.inf))
            gran = st["now"] * 6e-7 + TIME_EPS
            if use_slots:
                active = st["slot_edge"] >= 0
                rem = st["slot_rem"]
            else:
                active = st["f_started"] & ~st["f_done"] & needed
                rem = st["f_rem"]
            # double-where: see `body` — rate-0 lanes must not divide
            safe_rates = jnp.where(rates > 0, rates, 1.0)
            f_eta = jnp.where(active & (rates > 0), rem / safe_rates,
                              jnp.inf)
            f_eta = jnp.where(f_eta <= gran, 0.0, f_eta)
            f_next = st["now"] + jnp.min(f_eta, initial=jnp.inf)
            nxt = jnp.minimum(t_next, f_next)
            nxt = jnp.maximum(nxt, st["now"])          # never go back
            dt = jnp.where(jnp.isfinite(nxt), nxt - st["now"], 0.0)
            now = jnp.where(jnp.isfinite(nxt), nxt, st["now"])
            rem = jnp.where(active, rem - rates * dt, rem)
            done_now = active & ((rem <= BYTES_EPS) | (rem <= rates * gran))
            t_newly = running & (st["t_finish"] <= now + TIME_EPS)
            # released cores per worker as a dense [T, W] reduce (the
            # onehot is loop-invariant; an .at[assignment].add scatter
            # here costs ~10x more on XLA:CPU)
            free = st["free"] + jnp.sum(
                jnp.where(onehot_aw & t_newly[:, None], cpus[:, None], 0),
                axis=0, dtype=jnp.int32)
            st = dict(st, now=now, t_done=st["t_done"] | t_newly, free=free,
                      steps=st["steps"] + 1,
                      n_events=st["n_events"]
                      + jnp.sum(t_newly.astype(jnp.int32))
                      + jnp.sum(done_now.astype(jnp.int32)))
            if use_slots:
                se = st["slot_edge"]
                sec = jnp.clip(se, 0)
                # per-edge completion view for this event only —
                # satisfaction is folded into sat_cnt, so no f_done
                # carry survives
                newly_done_e = jnp.zeros(E, bool).at[sec].max(done_now)
                st = dict(st, slot_rem=rem,
                          slot_edge=jnp.where(done_now, -1, se),
                          transferred=st["transferred"]
                          + jnp.sum(jnp.where(done_now, f_bytes[sec], 0.0)))
            else:
                newly_done_e = done_now
                st = dict(st, f_rem=rem, f_done=st["f_done"] | done_now)
                if simple:
                    # no slot limits: produced flows start immediately
                    # (active from the next event on, like the baseline
                    # start at the top of the next body)
                    new_flow = needed & t_newly[prod_task_e]
                    st = dict(st, f_started=st["f_started"] | new_flow)
            # frontier maintenance: fold this event's completions into
            # the incremental counts, then append the new candidates
            moved_sat = cross & newly_done_e[rep]
            local_sat = t_newly[prod_task_e] & ~cross & edge_valid
            inc_sat = (moved_sat | local_sat).astype(jnp.int32)
            if simple:
                sat_cnt = (st["sat_cnt"]
                           + jnp.zeros(T, jnp.int32).at[e_task].add(inc_sat))
            else:
                # one fused scatter for both per-task counters (each
                # scatter call costs ~40us fixed on XLA:CPU)
                inc_in = (t_newly[prod_task_e] & edge_valid).astype(jnp.int32)
                both = (jnp.zeros(2 * T, jnp.int32)
                        .at[jnp.concatenate([e_task, e_task + T])]
                        .add(jnp.concatenate([inc_sat, inc_in])))
                sat_cnt = st["sat_cnt"] + both[:T]
            newly_en = ((sat_cnt >= n_inputs) & (st["sat_cnt"] < n_inputs)
                        & task_valid)
            fr_task, left_t = _frontier_append(st["fr_task"], newly_en,
                                               t_ids)
            st = dict(st, sat_cnt=sat_cnt, fr_task=fr_task,
                      overflow=st["overflow"] | (left_t > 0))
            if not simple:
                new_flow = needed & t_newly[prod_task_e]
                fr_flow, n_left = _frontier_append(st["fr_flow"], new_flow,
                                                   e_ids)
                st = dict(st, in_cnt=st["in_cnt"] + both[T:], fr_flow=fr_flow)

            if windowed:
                def defer(key_wait):
                    # the new keys that found the frontier full wait
                    with jax.named_scope(BACKLOG):
                        left = new_flow & ~_members(fr_flow, E)
                        return key_wait | jnp.zeros(O * W, bool).at[
                            key].max(left)

                n_wait = st["n_wait"] + n_left
                st = dict(st, key_wait=_run_once(n_left > 0, defer,
                                                 st["key_wait"]),
                          n_wait=n_wait,
                          backlog_peak=jnp.maximum(st["backlog_peak"],
                                                   n_wait))
            return st

        def cond(st):
            live = (~jnp.all(st["t_done"])) & (st["steps"] < steps_cap)
            if use_frontier:
                # an overflowed task frontier is no longer sound — stop
                # and report (ok is already poisoned by the flag)
                live = live & ~st["overflow"]
            return live

        st = jax.lax.while_loop(cond, body_frontier if use_frontier else body,
                                state0)
        makespan = jnp.max(jnp.where(st["t_done"] & task_valid,
                                     st["t_finish"], 0.0))
        if use_frontier and use_slots:
            transferred = st["transferred"]
        else:
            transferred = jnp.sum(jnp.where(needed & st["f_done"], f_bytes,
                                            0.0))
        ok = jnp.all(st["t_done"])
        overflow = st.get("overflow", jnp.bool_(False))
        ok = ok & ~overflow
        makespan = jnp.where(ok, makespan, jnp.nan)
        return SimResult(makespan, transferred, ok, overflow,
                         st["n_events"], st["steps"],
                         st.get("backlog_peak", jnp.int32(0)))

    return run


def make_simulator(spec: GraphSpec, n_workers: int, cores,
                   netmodel: str = "maxmin", flow_rounds: int = 4,
                   max_steps: int | None = None, **kwargs):
    """Deprecated per-graph binding of ``make_bucket_simulator`` —
    use ``repro.core.vectorized.api.build(spec, ...)`` (DESIGN.md §8).
    Returns ``run(assignment, priority, durations, sizes, bandwidth)
    -> SimResult`` with ``spec`` baked in."""
    warnings.warn(
        "make_simulator is deprecated; use "
        "repro.core.vectorized.api.build(spec, n_workers=..., cores=...) "
        "(DESIGN.md §8)", DeprecationWarning, stacklevel=2)
    bspec = as_bucketed(spec)
    brun = make_bucket_simulator(n_workers, cores, netmodel, flow_rounds,
                                 max_steps, **kwargs)

    def run(assignment, priority, durations=None, sizes=None,
            bandwidth=jnp.float32(100 * 1024 * 1024)):
        return brun(bspec, assignment, priority, durations, sizes, bandwidth)

    return run


def _onehot(bucket, n_buckets):
    """``bool[F, n_buckets]``: row ``f`` is True in column ``bucket[f]``.
    Every caller's buckets lie in ``[0, n_buckets)``, so each row holds
    exactly one True — the contract ``_bucket_read`` relies on."""
    return bucket[:, None] == jnp.arange(n_buckets,
                                         dtype=bucket.dtype)[None, :]


def _bucket_max(onehot, values):
    """Per-bucket max via a dense ``[F, n_buckets]`` masked reduce.
    Semantically identical to ``full(n_buckets, NEG).at[bucket].max(v)``
    (f32 max is order-independent) but scatter-free: XLA:CPU lowers
    every scatter to a ~40us library call inside a ``while_loop``,
    which dominates the event loop for the small bucket counts here.
    ``initial`` keeps the reduce defined for zero-length frontiers."""
    return jnp.max(jnp.where(onehot, values[:, None], NEG), axis=0,
                   initial=NEG)


def _bucket_read(onehot, table):
    """``table[bucket]``, read back to every row of ``onehot`` by a dense
    masked max along the bucket axis — exact for any value, because each
    row holds exactly one True (``_onehot``).  Gather-free: a dynamic
    gather inside the unrolled pick rounds runs about one index at a
    time on TPU (~13 ns an index on v5e, DESIGN.md §3), while this is
    the cost of the forward ``_bucket_max``."""
    if jnp.issubdtype(table.dtype, jnp.floating):
        fill = -jnp.inf
    else:
        fill = jnp.iinfo(table.dtype).min
    return jnp.max(jnp.where(onehot, table[None, :], fill), axis=1)


def _pick_per_bucket(onehot, eligible, *keys):
    """Lexicographic argmax per bucket of ``onehot`` (``_onehot``).
    ``keys`` are f32 arrays (higher wins); final tie broken by smallest
    element index.  Returns bool[F] with at most one True per bucket."""
    cand = eligible
    for k in keys:
        kk = jnp.where(cand, k, NEG)
        mb = _bucket_read(onehot, _bucket_max(onehot, kk))
        cand = cand & (kk == mb) & (mb > NEG)
    idx = jnp.arange(onehot.shape[0], dtype=jnp.float32)
    ii = jnp.where(cand, -idx, NEG)
    mb = _bucket_read(onehot, _bucket_max(onehot, ii))
    return cand & (ii == mb)


def _check_ok(ok, context: str, overflow=None):
    """Raise instead of letting NaN makespans leak into result tables."""
    ok = np.asarray(ok)
    if not ok.all():
        bad = int(ok.size - ok.sum())
        if overflow is not None and np.asarray(overflow).any():
            nov = int(np.asarray(overflow).sum())
            raise RuntimeError(
                f"{context}: {nov}/{ok.size} simulation(s) overflowed the "
                f"bounded task frontier (DESIGN.md §3) — widen "
                f"`frontier_caps` or run with `frontier=False`")
        raise RuntimeError(
            f"{context}: {bad}/{ok.size} simulation(s) exhausted their "
            f"max_steps event budget before all tasks finished (makespan "
            f"would be NaN) — the schedule likely leaves tasks unable to "
            f"start; raise max_steps only if the graph is genuinely that "
            f"deep")


def _check_cpus_fit(specs, cores, context: str):
    """Host-side guard shared by the runners: every task must fit the
    largest worker (the reference scheduler base raises the same way)."""
    max_cores = int(np.max(cores)) if np.size(cores) else 0
    for spec in specs:
        if spec.cpus.size and int(spec.cpus.max()) > max_cores:
            raise ValueError(
                f"{context}: a task needs {int(spec.cpus.max())} cores but "
                f"the largest worker has {max_cores}")


def simulate_batch(graph, assignments, priorities, n_workers, cores,
                   netmodel="maxmin", bandwidth=100 * 1024 * 1024.0):
    """Convenience: vmap over a batch of (assignment, priority).
    Returns ``(makespans, transferred_bytes)``; raises if any simulation
    in the batch failed to complete within its event budget."""
    bspec = as_bucketed(encode_graph(graph))
    brun = make_bucket_simulator(n_workers, cores, netmodel)
    fn = jax.jit(jax.vmap(
        lambda a, p: brun(bspec, a, p, bandwidth=bandwidth)))
    res = fn(jnp.asarray(assignments), jnp.asarray(priorities))
    _check_ok(res.ok, f"simulate_batch({graph.name!r})",
              res.overflow)
    return res.makespan, res.transferred


# ======================================================================
# dynamic scheduling: MSD + decision delay + imodes (paper §2, F4/F5)
# ======================================================================

def make_bucket_dynamic_simulator(n_workers: int, cores,
                                  scheduler: str = "blevel",
                                  netmodel: str = "maxmin",
                                  flow_rounds: int = 4,
                                  max_steps: int | None = None, *,
                                  max_cores: int | None = None, flow_slots=None,
                                  frontier=None, frontier_caps=None,
                                  waterfill_impl: str = "auto"):
    """Returns ``run(bspec, est_durations, est_sizes, msd, decision_delay,
    bandwidth, seed, cores) -> SimResult`` — a
    pure JAX function mirroring the reference simulator's event loop
    (``Simulator._step``) including its dynamic-scheduling machinery:

    * scheduler invocations are rate-limited by ``msd``; events (task
      completions / newly ready tasks) arriving in between are batched
      into the next invocation;
    * assignments take effect ``decision_delay`` seconds after the
      invocation that produced them;
    * the scheduler sees ``est_durations`` f32[T] / ``est_sizes`` f32[O]
      (from ``imodes.encode_imode``, padded with zeros to the bucket
      shape) for unfinished elements and true values for finished ones;
      the simulation itself always runs on ground truth.

    ``scheduler`` is one of ``vectorized.scheduling.VEC_SCHEDULERS``:
    the *static* family (``blevel``, ``tlevel``, ``mcp``, ``etf``,
    ``random`` — one schedule computed from the t=0 estimates, applied
    after the decision delay) or the *dynamic* ``greedy`` (ws-style
    greedy worker selection at every invocation).  Decisions match the
    deterministic reference twins (``blevel-det``, ``tlevel-det``,
    ``mcp-det``, ``etf-det``, ``random-det``, ``greedy`` —
    ``schedulers/det.py``).

    The graph is late-bound: the same trace serves every
    ``BucketedGraphSpec`` of one shape, and a stacked bucket batch plus
    the (msd x decision_delay x imode x bandwidth x seed) grid vmap into
    a single device call (``BucketedGridRunner``).  Padded entries are
    inert (mask semantics in the module docstring); padded/zero-core
    workers never receive tasks.

    Flows stay per input edge like the static path, but their
    destination — and the (object, destination) deduplication — is only
    known once the scheduler has assigned the consumer, so the dedup
    representative is pinned dynamically: the first edge whose download
    starts claims the (object, destination) key and every later
    same-key edge sees the object as already downloading/present.

    The keyword-only options mirror ``make_bucket_simulator``: a
    late-bound traced ``cores`` vector (build with ``cores=None`` + a
    static ``max_cores``), the bounded flow-slot pool on the max-min
    path (``flow_slots``), the routed max-min solver
    (``waterfill_impl``), and the ready-frontier compaction
    (``frontier``/``frontier_caps``).  The dynamic frontier derives
    in-flight flow state from the slot pool, so on the max-min path it
    requires ``flow_slots`` (the default); one fused O(E) detection
    pass per event feeds bounded candidate lists, and everything
    event-rate-dependent (flow pick rounds, Appendix-A start rounds,
    the greedy invoke's per-key views) runs on O(frontier)/O(S)
    entries.  A wanted key that finds the flow list full is not
    queued: the detection pass wants it again in the next iteration,
    and while any key waits the pick runs exactly over every edge, as
    in the static loop.  Tie-break caveat (greedy only): the dedup
    representative of an (object, destination) key is pinned when the
    key enters the flow list, so an exact cross-key priority tie can
    order picks by a different edge id than the baseline when a
    same-key edge with a smaller id becomes wanted later; static
    schedulers assign every consumer at one apply event, so their
    tie-breaks are exact.
    """
    if scheduler not in VEC_SCHEDULERS:
        raise KeyError(f"unknown vectorized scheduler {scheduler!r} "
                       f"(have {sorted(VEC_SCHEDULERS)})")
    W = n_workers
    cores_default = _resolve_cores(n_workers, cores)
    if max_cores is None:
        if cores_default is None:
            raise ValueError("max_cores is required when cores is None")
        max_cores = max(int(cores_default.max()), 1)
    max_cores = max(int(max_cores), 1)
    simple = netmodel == "simple"
    use_slots_cfg = (flow_slots is not False) and not simple
    use_frontier = _resolve_frontier(frontier, simple=simple,
                                     use_slots=use_slots_cfg, dynamic=True)
    wf = None if simple else _make_waterfill(waterfill_impl)
    S = W * DOWNLOAD_SLOTS
    slot_dst = jnp.arange(S, dtype=jnp.int32) // DOWNLOAD_SLOTS
    dynamic_sched = VEC_SCHEDULERS[scheduler] == "dynamic"

    if dynamic_sched:
        static_schedule = None
        greedy_place = make_bucket_greedy_placer(W, cores_default)
    else:
        static_schedule = make_bucket_scheduler(W, cores_default, scheduler,
                                                max_cores)
        greedy_place = None

    def run(bspec, est_durations, est_sizes, msd=jnp.float32(0.0),
            decision_delay=jnp.float32(0.0),
            bandwidth=jnp.float32(100 * 1024 * 1024), seed=jnp.int32(0),
            cores=None):
        _count_trace()
        bspec = as_jax(bspec)
        T, O, E = bspec.T, bspec.O, bspec.E
        F = O * W
        steps_cap = (max_steps if max_steps is not None
                     else 10 * (T + E) + 8 * W + 1024)
        if cores is None:
            if cores_default is None:
                raise ValueError("simulator built without a cluster: pass "
                                 "cores at call time")
            cores = cores_default
        cores_j = jnp.asarray(cores, jnp.int32)
        use_slots = use_slots_cfg and E > 0
        e_task, e_obj = bspec.edge_task, bspec.edge_obj
        producer, n_inputs, cpus = bspec.producer, bspec.n_inputs, bspec.cpus
        task_valid, obj_valid, edge_valid = (bspec.task_valid,
                                             bspec.obj_valid,
                                             bspec.edge_valid)
        durations_true = jnp.asarray(bspec.durations, jnp.float32)
        sizes_true = jnp.asarray(bspec.sizes, jnp.float32)
        e_ids = jnp.arange(E, dtype=jnp.int32)
        e_bytes = jnp.where(edge_valid, sizes_true[e_obj], 0.0)
        prod_task_e = producer[e_obj]              # producing task per edge
        # estimates are defensively masked: padded entries always 0, so
        # levels/costs of real tasks cannot depend on filler values
        est_dur = jnp.where(task_valid,
                            jnp.asarray(est_durations, jnp.float32), 0.0)
        est_size = jnp.where(obj_valid,
                             jnp.asarray(est_sizes, jnp.float32), 0.0)
        msd_ = jnp.asarray(msd, jnp.float32)
        delay = jnp.asarray(decision_delay, jnp.float32)
        bandwidth_ = jnp.asarray(bandwidth, jnp.float32)
        seed_ = jnp.asarray(seed, jnp.int32)

        with jax.named_scope(SCHEDULE):
            if dynamic_sched:
                greedy_prio = rank_priorities(bucket_blevel(bspec, est_dur))
                p_worker0 = jnp.full(T, -1, jnp.int32)
                p_prio0 = jnp.zeros(T, jnp.float32)
                p_time0 = jnp.full(T, jnp.inf, jnp.float32)
            else:
                # static schedule == the single invocation at t=0,
                # computed from pure estimates; it reaches workers after
                # the delay
                aw0, prio0 = static_schedule(bspec, est_dur, est_size,
                                             bandwidth_, seed_, cores_j)
                p_worker0 = jnp.where(task_valid, aw0, -1)
                p_prio0 = prio0
                p_time0 = jnp.where(task_valid, delay, jnp.inf)

        if frontier_caps is None:
            CF, CT = frontier_caps_for((T, O, E), n_workers=W)
        else:
            # an explicit override never exceeds the axis itself
            CF, CT = min(frontier_caps[0], E), min(frontier_caps[1], T)
        # the flow list holds distinct reps, so one of E entries can
        # hold every key at once: only a narrower one keeps a backlog
        windowed = use_frontier and use_slots and CF < E
        t_ids = jnp.arange(T, dtype=jnp.int32)

        state0 = dict(
            now=jnp.float32(0.0),
            last=NEG_TIME,                       # last scheduler invocation
            events=jnp.bool_(True),              # initial ready events
            aw=jnp.full(T, -1, jnp.int32),       # applied worker per task
            ap=jnp.zeros(T, jnp.float32),        # applied priority
            pw=p_worker0, pp=p_prio0, pt=p_time0,
            t_started=~task_valid,
            t_done=~task_valid,
            t_finish=jnp.full(T, jnp.inf, jnp.float32),
            free=cores_j.astype(jnp.int32),
            steps=jnp.int32(0),
            n_events=jnp.int32(0),
        )
        if not (use_frontier and use_slots):
            # frontier + slots: flow identity lives in the slot pool
            # and per-key bools; no per-edge flow carries at all
            state0.update(f_started=jnp.zeros(E, bool),  # flow = input edge
                          f_done=jnp.zeros(E, bool))
        if use_slots:
            state0.update(
                slot_edge=jnp.full(S, -1, jnp.int32),
                slot_src=jnp.zeros(S, jnp.int32),
                slot_rem=jnp.zeros(S, jnp.float32),
                overflow=jnp.bool_(False),
            )
        else:
            state0["f_rem"] = e_bytes
        if use_frontier:
            # assignments arrive over time, so every frontier starts
            # empty: the per-event detection pass appends as tasks gain
            # (producer-done, consumer-assigned) pairs
            state0.setdefault("overflow", jnp.bool_(False))
            state0.update(
                enq_t=jnp.zeros(T, bool),        # ever-enqueued tasks
                in_cnt=jnp.zeros(T, jnp.int32),  # produced valid inputs
                fr_task=jnp.full(CT, -1, jnp.int32),
            )
            if E > 0:
                state0.update(key_q=jnp.zeros(F, bool),
                              key_done=jnp.zeros(F, bool))
                if use_slots:
                    state0.update(fr_flow=jnp.full(CF, -1, jnp.int32),
                                  transferred=jnp.float32(0.0))
                if windowed:
                    state0["backlog_peak"] = jnp.int32(0)

        # ------------------------------------------------ shared views
        def edge_views(st):
            """(consumer worker, producer worker, (obj, dst) dedup key)
            per input edge; keys are only meaningful for assigned
            consumers of *valid* edges — everything scattered through
            them is masked so the clip-to-0 of unassigned or padded
            edges never pollutes."""
            aw_e = st["aw"][e_task]
            src_e = st["aw"][prod_task_e]
            key_e = e_obj * W + jnp.clip(aw_e, 0)
            return aw_e, src_e, key_e

        def key_reduce_or(key_e, values):
            return jnp.zeros(F, bool).at[key_e].max(values)

        def produced_of(st):
            return st["t_done"][producer]                       # bool[O]

        def inputs_produced(st):
            prod_e = st["t_done"][prod_task_e] & edge_valid
            cnt = (jnp.zeros(T, jnp.int32)
                   .at[e_task].add(prod_e.astype(jnp.int32)))
            return cnt >= n_inputs                              # bool[T]

        # --------------------------------------------------- scheduler
        def apply_due(st):
            due = (st["pw"] >= 0) & (st["pt"] <= st["now"] + TIME_EPS)
            return dict(
                st,
                aw=jnp.where(due, st["pw"], st["aw"]),
                ap=jnp.where(due, st["pp"], st["ap"]),
                pw=jnp.where(due, -1, st["pw"]),
                pt=jnp.where(due, jnp.inf, st["pt"]),
            )

        def invoke(st):
            due = st["events"] & (st["last"] + msd_ <= st["now"] + TIME_EPS)
            if E == 0:
                cost_tw = jnp.zeros((T, W), jnp.float32)
            else:
                prod = produced_of(st)
                prod_w = st["aw"][producer]
                if use_frontier and use_slots:
                    # per-key views come straight from the carried key
                    # bools and the S-slot pool — no O(E) reduce here
                    done_ow = st["key_done"].reshape(O, W)
                    sk = e_obj[jnp.clip(st["slot_edge"], 0)] * W + slot_dst
                    dl_ow = (jnp.zeros(F, bool)
                             .at[sk].max(st["slot_edge"] >= 0)
                             .reshape(O, W))
                else:
                    _, _, key_e = edge_views(st)
                    done_ow = key_reduce_or(key_e, st["f_done"]).reshape(O, W)
                    dl_ow = key_reduce_or(
                        key_e, st["f_started"] & ~st["f_done"]).reshape(O, W)
                local_ow = (prod_w[:, None] == jnp.arange(W)[None, :]) \
                    & prod[:, None]
                missing = ~(local_ow | done_ow | dl_ow)
                size_now = jnp.where(prod, sizes_true, est_size)
                cost_tw = bucket_transfer_costs(bspec, size_now, missing)
            ready_t = (st["in_cnt"] >= n_inputs) if use_frontier \
                else inputs_produced(st)
            ready_un = (ready_t & (st["aw"] < 0)
                        & (st["pw"] < 0) & ~st["t_done"])
            queued = (((st["aw"] >= 0) | (st["pw"] >= 0))
                      & ~st["t_started"] & ~st["t_done"])
            qworker = jnp.where(st["aw"] >= 0, st["aw"], st["pw"])
            load0 = (jnp.zeros(W, jnp.int32)
                     .at[jnp.clip(qworker, 0)].add(queued.astype(jnp.int32)))
            # a lane that is not due discards its placement, so it
            # places nothing and costs the placer's loop no trip
            new_pw = greedy_place(bspec, ready_un & due, cost_tw, load0,
                                  cores_j)
            newly = due & (new_pw >= 0)
            return dict(
                st,
                pw=jnp.where(newly, new_pw, st["pw"]),
                pp=jnp.where(newly, greedy_prio, st["pp"]),
                pt=jnp.where(newly, st["now"] + delay, st["pt"]),
                events=st["events"] & ~due,
                last=jnp.where(due, st["now"], st["last"]),
            )

        # ----------------------------------------------------- workers
        def start_flows(st):
            if E == 0:       # no data objects => no network at all
                return st
            aw_e, src_e, key_e = edge_views(st)
            prod_e = st["t_done"][prod_task_e]
            cross = ((aw_e >= 0) & (src_e >= 0) & (src_e != aw_e)
                     & edge_valid)
            # download priority: max over same-key edges, ready boosted
            ready = inputs_produced(st)
            raw = st["ap"][e_task] + READY_BOOST * \
                ready[e_task].astype(jnp.float32)
            raw = jnp.where((aw_e >= 0) & edge_valid, raw, NEG)
            f_prio = (jnp.full(F, NEG, jnp.float32)
                      .at[key_e].max(raw))[key_e]
            bucket = jnp.clip(aw_e, 0)
            if simple:
                handled = key_reduce_or(key_e, st["f_started"])
                eligible = cross & prod_e & ~handled[key_e]
                # dedup within this wave: smallest edge id per key starts
                rep = (jnp.full(F, E, jnp.int32)
                       .at[key_e].min(jnp.where(eligible, e_ids, E)))
                pick = eligible & (rep[key_e] == e_ids)
                return dict(st, f_started=st["f_started"] | pick)
            pair = jnp.clip(src_e, 0) * W + bucket
            onehot = _onehot(bucket, W)
            # round-invariant eligibility base; the handled-key mask and
            # slot limits are what this event's own picks update
            base = cross & prod_e & ~key_reduce_or(key_e,
                                                   st["f_started"])[key_e]
            for _ in range(flow_rounds):
                if use_slots:
                    occ = st["slot_edge"] >= 0
                    dcnt = (occ.reshape(W, DOWNLOAD_SLOTS)
                            .sum(axis=1, dtype=jnp.int32))
                    pair_s = st["slot_src"] * W + slot_dst
                    pcnt = (jnp.zeros(W * W, jnp.int32)
                            .at[pair_s].add(occ.astype(jnp.int32)))
                else:
                    active = (st["f_started"]
                              & ~st["f_done"]).astype(jnp.int32)
                    dcnt = jnp.zeros(W, jnp.int32).at[bucket].add(active)
                    pcnt = jnp.zeros(W * W, jnp.int32).at[pair].add(active)
                eligible = (base
                            & (_bucket_read(onehot, dcnt) < DOWNLOAD_SLOTS)
                            & (pcnt[pair] < PAIR_SLOTS))
                # same key => same bucket, so one pick also dedups; all
                # same-key edges leave the base once one of them starts
                pick = _pick_per_bucket(onehot, eligible, f_prio)
                base = base & ~key_reduce_or(key_e, pick)[key_e]
                st = dict(st, f_started=st["f_started"] | pick)
                if use_slots:
                    st = _acquire_slots(st, pick, onehot,
                                        jnp.clip(src_e, 0), e_bytes)
            return st

        def edge_satisfied(st):
            aw_e, src_e, key_e = edge_views(st)
            prod_done = st["t_done"][prod_task_e]
            local = prod_done & (src_e == aw_e)
            moved = key_reduce_or(key_e, st["f_done"])[key_e]
            return (aw_e >= 0) & (local | moved) & edge_valid

        def start_tasks(st):
            if E == 0:
                enabled = ~st["t_started"] & (st["aw"] >= 0)
            else:
                sat = edge_satisfied(st).astype(jnp.int32)
                cnt = jnp.zeros(T, jnp.int32).at[e_task].add(sat)
                enabled = (cnt >= n_inputs) & ~st["t_started"] \
                    & (st["aw"] >= 0)
            bucket = jnp.clip(st["aw"], 0)
            onehot = _onehot(bucket, W)
            for _ in range(max_cores):
                free_at = _bucket_read(onehot, st["free"])
                waiting = enabled & ~st["t_started"]
                blocked = waiting & (cpus > free_at)
                maxblk = jnp.full(W, NEG, jnp.float32).at[bucket].max(
                    jnp.where(blocked, st["ap"], NEG))
                cand = (waiting & (cpus <= free_at)
                        & (st["ap"] >= _bucket_read(onehot, maxblk)))
                pick = _pick_per_bucket(onehot, cand, st["ap"])
                st = dict(
                    st,
                    t_started=st["t_started"] | pick,
                    t_finish=jnp.where(pick, st["now"] + durations_true,
                                       st["t_finish"]),
                    free=st["free"] - jnp.zeros(W, jnp.int32)
                    .at[bucket].add(jnp.where(pick, cpus, 0)),
                )
            return st

        def start_flows_frontier(st, keymax, waiting):
            """Max-min flow picks over the pinned candidate list; the
            slot pool is required (in-flight state and the Appendix-A
            occupancy live there).  ``keymax`` is this event's priority
            scatter-max from the detection pass, gathered only at the
            CF candidates; ``-edge_id`` reproduces the baseline
            tie-break (exact for static schedulers, see factory
            docstring for the greedy caveat).  A lane whose keys are
            ``waiting`` outside the list picks nothing here: the
            backlog's exact pick serves it."""
            fr = st["fr_flow"]
            cid = jnp.clip(fr, 0)
            c_dst = jnp.clip(st["aw"][e_task[cid]], 0)
            alive = fr >= 0 if waiting is None else (fr >= 0) & ~waiting
            st, picked = _flow_pick_rounds(
                st, alive, c_dst,
                jnp.clip(st["aw"][prod_task_e[cid]], 0),
                keymax[e_obj[cid] * W + c_dst], e_bytes[cid], fr,
                flow_rounds=flow_rounds)
            return dict(st, fr_flow=jnp.where(picked, -1, fr))

        def start_tasks_frontier(st):
            """Appendix-A start rounds over the bounded enabled list —
            invariantly exactly the enabled & assigned & not-started
            tasks, so blocking matches the full [T] scan."""
            fr = st["fr_task"]
            tid = jnp.clip(fr, 0)
            alive = fr >= 0
            c_w = jnp.clip(st["aw"][tid], 0)
            c_cpus = cpus[tid]
            c_prio = st["ap"][tid]
            c_fin = durations_true[tid]
            neg_id = -fr.astype(jnp.float32)
            alive0 = alive
            free = st["free"]
            onehot_w = _onehot(c_w, W)
            for _ in range(max_cores):
                free_at = _bucket_read(onehot_w, free)
                blocked = alive & (c_cpus > free_at)
                maxblk = _bucket_max(onehot_w,
                                     jnp.where(blocked, c_prio, NEG))
                cand = (alive & (c_cpus <= free_at)
                        & (c_prio >= _bucket_read(onehot_w, maxblk)))
                pick = _pick_per_bucket(onehot_w, cand, c_prio, neg_id)
                # <= 1 pick per worker, so the core delta is a dense
                # [C, W] masked max, and the started/finish writes can
                # wait: every round shares st["now"]
                free = free - jnp.max(jnp.where(onehot_w & pick[:, None],
                                                c_cpus[:, None], 0), axis=0,
                                      initial=0)
                alive = alive & ~pick
            newly = alive0 & ~alive
            dest = jnp.where(newly, fr, T)
            started = st["t_started"].at[dest].set(True, mode="drop")
            t_finish = st["t_finish"].at[dest].set(st["now"] + c_fin,
                                                   mode="drop")
            return dict(st, t_started=started, t_finish=t_finish, free=free,
                        fr_task=jnp.where(newly, -1, fr))

        def rates_of(st):
            if E == 0 or simple:
                active = st["f_started"] & ~st["f_done"]
                return jnp.where(active, bandwidth_, 0.0)
            caps = jnp.full(W, bandwidth_, jnp.float32)
            if use_slots:
                occ = st["slot_edge"] >= 0
                return wf(st["slot_src"], slot_dst, occ, caps)
            aw_e, src_e, _ = edge_views(st)
            active = st["f_started"] & ~st["f_done"]
            return wf(jnp.clip(src_e, 0), jnp.clip(aw_e, 0), active, caps)

        # -------------------------------------------------------- body
        # each iteration runs the four SIM_PHASES in order, each under
        # its own named scope
        def schedule(st):
            st = apply_due(st)
            if dynamic_sched:
                st = invoke(st)
                st = apply_due(st)           # decision_delay == 0
            return st

        def body(st):
            with jax.named_scope(SCHEDULE):
                st = schedule(st)
            with jax.named_scope(READY):
                st = start_tasks(start_flows(st))
            with jax.named_scope(RATES):
                rates = rates_of(st)
            with jax.named_scope(ADVANCE):
                return advance(st, rates)

        def advance(st, rates):
            running = st["t_started"] & ~st["t_done"]
            t_next = jnp.min(jnp.where(running, st["t_finish"], jnp.inf))
            gran = st["now"] * 6e-7 + TIME_EPS
            if use_slots:
                active = st["slot_edge"] >= 0
                rem = st["slot_rem"]
            else:
                active = st["f_started"] & ~st["f_done"]
                rem = st["f_rem"]
            # double-where: unselected lanes still evaluate the division,
            # so the denominator needs its own guard or rate-0 lanes
            # produce inf*0/NaN that poison min-reductions downstream
            safe_rates = jnp.where(rates > 0, rates, 1.0)
            f_eta = jnp.where(active & (rates > 0), rem / safe_rates,
                              jnp.inf)
            f_eta = jnp.where(f_eta <= gran, 0.0, f_eta)
            f_next = st["now"] + jnp.min(f_eta, initial=jnp.inf)
            nxt = jnp.minimum(t_next, f_next)
            # pending-apply times are inf when unset and padded tasks
            # never get a pending slot, so the unmasked min is exact
            nxt = jnp.minimum(nxt, jnp.min(st["pt"]))  # simlint: disable=PY205
            if dynamic_sched:
                sched_next = jnp.where(
                    st["events"], jnp.maximum(st["now"], st["last"] + msd_),
                    jnp.inf)
                nxt = jnp.minimum(nxt, sched_next)
            nxt = jnp.maximum(nxt, st["now"])          # never go back
            dt = jnp.where(jnp.isfinite(nxt), nxt - st["now"], 0.0)
            now = jnp.where(jnp.isfinite(nxt), nxt, st["now"])
            rem = jnp.where(active, rem - rates * dt, rem)
            done_now = active & ((rem <= BYTES_EPS) | (rem <= rates * gran))
            t_newly = running & (st["t_finish"] <= now + TIME_EPS)
            free = st["free"] + jnp.zeros(W, jnp.int32).at[
                jnp.clip(st["aw"], 0)].add(jnp.where(t_newly, cpus, 0))
            st = dict(st, now=now, t_done=st["t_done"] | t_newly, free=free,
                      events=st["events"] | jnp.any(t_newly),
                      steps=st["steps"] + 1,
                      n_events=st["n_events"]
                      + jnp.sum(t_newly.astype(jnp.int32))
                      + jnp.sum(done_now.astype(jnp.int32)))
            if use_slots:
                newly_done = (jnp.zeros(E, bool)
                              .at[jnp.clip(st["slot_edge"], 0)].max(done_now))
                return dict(st, slot_rem=rem,
                            slot_edge=jnp.where(done_now, -1,
                                                st["slot_edge"]),
                            f_done=st["f_done"] | newly_done)
            return dict(st, f_rem=rem, f_done=st["f_done"] | done_now)

        def body_frontier(st):
            with jax.named_scope(SCHEDULE):
                st = schedule(st)
            with jax.named_scope(READY):
                st, key_e = ready_frontier(st)
            with jax.named_scope(RATES):
                rates = rates_of(st)
            with jax.named_scope(ADVANCE):
                return advance_frontier(st, rates, key_e)

        def ready_frontier(st):
            """Returns the state and the edges' (object, destination)
            keys, which the simple model's advance reuses."""
            # fused O(E) detection pass — the only per-edge work in the
            # loop: new (producer-done, consumer-assigned) pairs become
            # flow candidates (dedup rep pinned per key) and satisfied
            # edges; everything below runs on the bounded frontiers
            ready_t = st["in_cnt"] >= n_inputs
            keymax = key_e = None
            if E > 0:
                aw_e = st["aw"][e_task]
                src_e = st["aw"][prod_task_e]
                key_e = e_obj * W + jnp.clip(aw_e, 0)
                assigned = (aw_e >= 0) & edge_valid
                prod_e = st["t_done"][prod_task_e]
                cross = assigned & (src_e >= 0) & (src_e != aw_e)
                raw = st["ap"][e_task] + READY_BOOST * \
                    ready_t[e_task].astype(jnp.float32)
                raw = jnp.where(assigned, raw, NEG)
                keymax = jnp.full(F, NEG, jnp.float32).at[key_e].max(raw)
                # key_q marks the keys whose rep is on the flow list or
                # has started; a wanted key that found the list full is
                # wanted again, so it is offered again in each iteration
                want = cross & prod_e & ~st["key_q"][key_e]
                rep = (jnp.full(F, E, jnp.int32)
                       .at[key_e].min(jnp.where(want, e_ids, E)))
                new_flow = want & (rep[key_e] == e_ids)
                sat = assigned & ((prod_e & (src_e == aw_e))
                                  | st["key_done"][key_e])
                sat_cnt = (jnp.zeros(T, jnp.int32)
                           .at[e_task].add(sat.astype(jnp.int32)))
                enabled = ((sat_cnt >= n_inputs) & (st["aw"] >= 0)
                           & ~st["t_started"])
                if use_slots:
                    fr_flow, n_left = _frontier_append(st["fr_flow"],
                                                       new_flow, e_ids)
                    # rep < E exactly marks the keys that just queued a
                    # rep, so where every one fit key_q updates as a
                    # dense [F] mask — no scatter
                    queued = rep < E
                    waiting = None
                    if windowed:
                        waiting = n_left > 0
                        queued = queued & ~waiting
                        st = dict(st, backlog_peak=jnp.maximum(
                            st["backlog_peak"], n_left))
                    st = dict(st, fr_flow=fr_flow,
                              key_q=st["key_q"] | queued)
                else:
                    # simple netmodel: no slot limits — pinned reps
                    # start the moment they become wanted, exactly the
                    # baseline's immediate-start semantics
                    st = dict(st, key_q=st["key_q"] | (rep < E),
                              f_started=st["f_started"] | new_flow)
            else:
                enabled = (st["aw"] >= 0) & ~st["t_started"]
            new_en = enabled & ~st["enq_t"]
            fr_task, left_t = _frontier_append(st["fr_task"], new_en, t_ids)
            st = dict(st, fr_task=fr_task, enq_t=st["enq_t"] | new_en,
                      overflow=st["overflow"] | (left_t > 0))
            if E > 0 and use_slots:
                st = start_flows_frontier(st, keymax, waiting)

            if windowed:
                def drain(sub):
                    # the exact pick over the list and the waiting reps;
                    # the keys that entered the list or started leave
                    # the backlog, the rest are wanted again next time
                    with jax.named_scope(BACKLOG):
                        sub, fr, members, picked = _backlog_rounds(
                            sub, sub["fr_flow"], new_flow, jnp.clip(aw_e, 0),
                            jnp.clip(src_e, 0), keymax[key_e], e_bytes,
                            flow_rounds=flow_rounds)
                        return dict(sub, fr_flow=fr, key_q=sub["key_q"]
                                    | jnp.zeros(F, bool).at[key_e].max(
                                        new_flow & (members | picked)))

                moved = ("fr_flow", "key_q", "slot_edge", "slot_src",
                         "slot_rem", "overflow")
                st = dict(st, **_run_once(waiting, drain,
                                          {k: st[k] for k in moved}))
            return start_tasks_frontier(st), key_e

        def advance_frontier(st, rates, key_e):
            running = st["t_started"] & ~st["t_done"]
            t_next = jnp.min(jnp.where(running, st["t_finish"], jnp.inf))
            gran = st["now"] * 6e-7 + TIME_EPS
            if use_slots:
                active = st["slot_edge"] >= 0
                rem = st["slot_rem"]
            else:
                active = st["f_started"] & ~st["f_done"]
                rem = st["f_rem"]
            # double-where: see `body` — rate-0 lanes must not divide
            safe_rates = jnp.where(rates > 0, rates, 1.0)
            f_eta = jnp.where(active & (rates > 0), rem / safe_rates,
                              jnp.inf)
            f_eta = jnp.where(f_eta <= gran, 0.0, f_eta)
            f_next = st["now"] + jnp.min(f_eta, initial=jnp.inf)
            nxt = jnp.minimum(t_next, f_next)
            nxt = jnp.minimum(nxt, jnp.min(st["pt"]))  # simlint: disable=PY205
            if dynamic_sched:
                sched_next = jnp.where(
                    st["events"], jnp.maximum(st["now"], st["last"] + msd_),
                    jnp.inf)
                nxt = jnp.minimum(nxt, sched_next)
            nxt = jnp.maximum(nxt, st["now"])          # never go back
            dt = jnp.where(jnp.isfinite(nxt), nxt - st["now"], 0.0)
            now = jnp.where(jnp.isfinite(nxt), nxt, st["now"])
            rem = jnp.where(active, rem - rates * dt, rem)
            done_now = active & ((rem <= BYTES_EPS) | (rem <= rates * gran))
            t_newly = running & (st["t_finish"] <= now + TIME_EPS)
            # finished tasks all have aw >= 0, so the dense [T, W] reduce
            # (aw is state here, unlike the static path's fixed axis)
            # replaces the free scatter exactly
            onehot_aw = st["aw"][:, None] == jnp.arange(
                W, dtype=jnp.int32)[None, :]
            free = st["free"] + jnp.sum(
                jnp.where(onehot_aw & t_newly[:, None], cpus[:, None], 0),
                axis=0, dtype=jnp.int32)
            in_cnt = st["in_cnt"] + jnp.zeros(T, jnp.int32).at[e_task].add(
                (t_newly[prod_task_e] & edge_valid).astype(jnp.int32))
            st = dict(st, now=now, t_done=st["t_done"] | t_newly, free=free,
                      events=st["events"] | jnp.any(t_newly),
                      in_cnt=in_cnt, steps=st["steps"] + 1,
                      n_events=st["n_events"]
                      + jnp.sum(t_newly.astype(jnp.int32))
                      + jnp.sum(done_now.astype(jnp.int32)))
            if use_slots:
                se = st["slot_edge"]
                sec = jnp.clip(se, 0)
                # a finished slot completes its whole (obj, dst) key:
                # every same-key edge is satisfied through key_done
                sk = e_obj[sec] * W + slot_dst
                return dict(st, slot_rem=rem,
                            slot_edge=jnp.where(done_now, -1, se),
                            key_done=st["key_done"].at[sk].max(done_now),
                            transferred=st["transferred"]
                            + jnp.sum(jnp.where(done_now, e_bytes[sec],
                                                0.0)))
            st = dict(st, f_rem=rem, f_done=st["f_done"] | done_now)
            if E > 0:
                st = dict(st,
                          key_done=st["key_done"].at[key_e].max(done_now))
            return st

        def cond(st):
            live = (~jnp.all(st["t_done"])) & (st["steps"] < steps_cap)
            if use_frontier:
                # an overflowed task frontier is no longer sound — stop
                # and report (ok is already poisoned by the flag)
                live = live & ~st["overflow"]
            return live

        st = jax.lax.while_loop(cond, body_frontier if use_frontier else body,
                                state0)
        makespan = jnp.max(jnp.where(st["t_done"] & task_valid,
                                     st["t_finish"], 0.0))
        if use_frontier and use_slots:
            transferred = st["transferred"]
        else:
            transferred = jnp.sum(jnp.where(st["f_done"], e_bytes, 0.0))
        ok = jnp.all(st["t_done"])
        overflow = st.get("overflow", jnp.bool_(False))
        ok = ok & ~overflow
        makespan = jnp.where(ok, makespan, jnp.nan)
        return SimResult(makespan, transferred, ok, overflow,
                         st["n_events"], st["steps"],
                         st.get("backlog_peak", jnp.int32(0)))

    return run


def make_dynamic_simulator(spec: GraphSpec, n_workers: int, cores,
                           scheduler: str = "blevel",
                           netmodel: str = "maxmin", flow_rounds: int = 4,
                           max_steps: int | None = None, **kwargs):
    """Deprecated per-graph binding of ``make_bucket_dynamic_simulator``
    — use ``repro.core.vectorized.api.build(spec, scheduler=...,
    dynamic=True)`` (DESIGN.md §8).  Returns ``run(est_durations,
    est_sizes, msd, decision_delay, bandwidth, seed) -> SimResult`` with
    ``spec`` baked in; all six arguments are batchable under
    ``jax.vmap``."""
    warnings.warn(
        "make_dynamic_simulator is deprecated; use "
        "repro.core.vectorized.api.build(spec, scheduler=..., "
        "dynamic=True) (DESIGN.md §8)", DeprecationWarning, stacklevel=2)
    cores_v = _resolve_cores(n_workers, cores)
    _check_cpus_fit([spec], cores_v, "make_dynamic_simulator")
    bspec = as_bucketed(spec)
    brun = make_bucket_dynamic_simulator(n_workers, cores_v, scheduler,
                                         netmodel, flow_rounds, max_steps,
                                         **kwargs)

    def run(est_durations, est_sizes, msd=jnp.float32(0.0),
            decision_delay=jnp.float32(0.0),
            bandwidth=jnp.float32(100 * 1024 * 1024), seed=jnp.int32(0)):
        return brun(bspec, est_durations, est_sizes, msd, decision_delay,
                    bandwidth, seed)

    return run


def _points_arrays(points):
    points = list(points)
    if not points:
        raise ValueError("dynamic grid needs at least one point "
                         "(got an empty points iterable)")
    M = np.array([p.get("msd", 0.0) for p in points], np.float32)
    DD = np.array([p.get("decision_delay", 0.0) for p in points],
                  np.float32)
    BW = np.array([p.get("bandwidth", 100 * 1024 * 1024.0)
                   for p in points], np.float32)
    SD = np.array([p.get("seed", 0) for p in points], np.int32)
    return points, M, DD, BW, SD


class DynamicGridRunner:
    """Reusable jit-compiled dynamic-grid executor for one
    (graph, scheduler, cluster, netmodel).

    Build once, then call with any number of grid points; the compiled
    program and the per-imode estimate encodings are cached, so repeated
    sweeps (benchmark loops, GA generations, dashboards) pay tracing and
    XLA compilation exactly once per batch shape.  Pass a prebuilt
    ``spec`` (``encode_graph(graph)``) to share the dense encoding when
    many runners sweep the same graph.  ``cores`` may be a scalar or a
    per-worker list (heterogeneous cluster).  For whole graph *sets*
    sharing one compilation, see ``BucketedGridRunner``.
    """

    def __init__(self, graph, scheduler, n_workers, cores,
                 netmodel="maxmin", max_steps=None, spec=None):
        self.graph = graph
        self.scheduler = scheduler
        if spec is None:
            spec = encode_graph(graph)
        from .api import build
        self.run = build(spec, n_workers=n_workers, cores=cores,
                         scheduler=scheduler, netmodel=netmodel,
                         dynamic=True, max_steps=max_steps)
        self._fn = jax.jit(jax.vmap(self.run))
        self._est = {}

    def _estimates(self, name):
        if name not in self._est:
            from ..imodes import encode_imode
            self._est[name] = encode_imode(self.graph, name)
        return self._est[name]

    def __call__(self, points):
        """``points``: iterable of dicts with keys ``msd``,
        ``decision_delay``, ``imode``, ``bandwidth`` and ``seed``
        (missing keys default to 0 / "exact" / 100 MiB/s / 0; ``seed``
        only matters for the counter-based ``random`` scheduler).
        Returns ``(makespans f32[N], transferred f32[N])`` in point
        order; raises if any grid point exhausted its event budget."""
        points, M, DD, BW, SD = _points_arrays(points)
        D = np.stack([self._estimates(p.get("imode", "exact"))[0]
                      for p in points])
        S = np.stack([self._estimates(p.get("imode", "exact"))[1]
                      for p in points])
        res = self._fn(D, S, M, DD, BW, SD)
        _check_ok(res.ok, f"simulate_dynamic_grid({self.graph.name!r}, "
                          f"{self.scheduler!r})", res.overflow)
        return np.asarray(res.makespan), np.asarray(res.transferred)


class BucketedGridRunner:
    """One jit compilation for a whole *shape bucket* of graphs on a
    whole group of same-W clusters for one (scheduler, netmodel).

    ``entries`` is ``[(graph, spec), ...]`` (or ``{name: (graph,
    spec)}``); every member is padded to the common bucket shape
    (``shape`` or ``specs.bucket_shape``) and stacked along a graph vmap
    axis, so ``__call__(points)`` executes the full [graphs x points]
    grid — estimates, msd, delay, bandwidth, seed — in a single device
    call compiled exactly once (the survey's one-compile-per-bucket
    contract; measured by ``trace_counter``).

    ``cores`` is a scalar, a per-worker list (heterogeneous cluster,
    e.g. ``1x8+4x2``), or a stacked ``[K, W]`` matrix of K same-W
    cluster signatures (pad shorter clusters with zero-core workers):
    the cores vector is a *traced argument* of the compiled program, so
    the whole cluster group rides one compilation as an extra vmap axis
    and results gain a leading ``K`` axis.

    When many runners sweep the same bucket (the survey's cluster x
    scheduler x netmodel fan-out), pass the prestacked ``batch``
    (``BucketGroup.batch``) and a shared ``est_cache`` dict so the
    padding/stacking and per-imode estimate encodings are computed once
    per bucket instead of once per runner.
    """

    def __init__(self, entries, scheduler, n_workers, cores,
                 netmodel="maxmin", max_steps=None, shape=None,
                 batch=None, est_cache=None):
        from .engine import setup_span

        # encoding, padding and stacking the graphs and building
        # the simulator are set-up on the host
        with setup_span("host"):
            if isinstance(entries, dict):
                entries = list(entries.values())
            entries = [(g, encode_graph(g) if s is None else s)
                       for g, s in entries]
            self.graphs = [g for g, _ in entries]
            self.specs = [s for _, s in entries]
            self.names = [g.name for g in self.graphs]
            self.scheduler = scheduler
            arr = np.asarray(cores)
            if arr.ndim <= 1:
                clusters = _resolve_cores(n_workers, cores)[None, :]
                self._single_cluster = True
            else:
                clusters = arr.astype(np.int32)
                self._single_cluster = False
            if clusters.shape[-1] != n_workers:
                raise ValueError(f"cores matrix is {clusters.shape[-1]} wide "
                                 f"but n_workers={n_workers}")
            self.clusters = clusters
            for k in range(clusters.shape[0]):
                _check_cpus_fit(self.specs, clusters[k],
                                f"BucketedGridRunner({scheduler!r})")
            self.shape = tuple(shape) if shape is not None \
                else bucket_shape(self.specs)
            if batch is not None:
                if batch.shape != self.shape or batch.B != len(self.specs):
                    raise ValueError(
                        f"prebuilt batch {batch.shape}xB{batch.B} does not "
                        f"match {self.shape}xB{len(self.specs)}")
                self.bspec = batch
            else:
                self.bspec = stack_specs([pad_spec(s, self.shape)
                                          for s in self.specs])
            from .api import build
            self.run = build(None, n_workers=n_workers, cores=None,
                             scheduler=scheduler, netmodel=netmodel,
                             dynamic=True, max_steps=max_steps,
                             max_cores=max(int(clusters.max()), 1))
            self._fn = self._make_fn()
            self._est = {} if est_cache is None else est_cache

    def _make_fn(self):
        """The compiled grid program: vmap clusters K x graphs B x
        points N around ``self.run`` under one jit.  Subclass hook —
        ``ShardedGridRunner`` (engine.py) replaces the single-device
        nest with a shard_map over a 1-D device mesh."""
        over_points = jax.vmap(self.run,
                               in_axes=(None, 0, 0, 0, 0, 0, 0, None))
        over_graphs = jax.vmap(over_points,
                               in_axes=(0, 0, 0, None, None, None, None,
                                        None))
        return jax.jit(jax.vmap(over_graphs,
                                in_axes=(None, None, None, None, None,
                                         None, None, 0)))

    def _execute(self, D, S, M, DD, BW, SD):
        """One device call over the whole [K, B, N] grid.  Subclass
        hook — the sharded engine reshapes to flat rows, pads to the
        device count and streams chunks through a prefetch queue, but
        must return the same ``SimResult[K, B, N]``."""
        return self._fn(self.bspec, D, S, M, DD, BW, SD, self.clusters)

    @property
    def B(self):
        return len(self.graphs)

    def _estimates(self, name):
        """Padded, stacked estimates for one imode: (f32[B, T], f32[B, O])."""
        if name not in self._est:
            from ..imodes import encode_imode
            from .engine import setup_span
            T, O, _ = self.shape
            with setup_span("host"):
                ds, ss = [], []
                for g in self.graphs:
                    d, s = encode_imode(g, name)
                    ds.append(pad_to(d, T))
                    ss.append(pad_to(s, O))
                self._est[name] = (np.stack(ds), np.stack(ss))
        return self._est[name]

    def grid_arrays(self, points):
        """Host arrays ``(D, S, M, DD, BW, SD)`` of one call over
        ``points`` — what ``_execute`` takes."""
        points, M, DD, BW, SD = _points_arrays(points)
        # [B, N, T] / [B, N, O]: per point the whole graph batch sees
        # that point's imode estimates
        D = np.stack([self._estimates(p.get("imode", "exact"))[0]
                      for p in points], axis=1)
        S = np.stack([self._estimates(p.get("imode", "exact"))[1]
                      for p in points], axis=1)
        return D, S, M, DD, BW, SD

    def __call__(self, points):
        """Same point dicts as ``DynamicGridRunner``; returns
        ``(makespans f32[B, N], transferred f32[B, N])`` with the graph
        axis in ``self.names`` order — with a leading cluster axis
        (``f32[K, B, N]``) when built with a ``[K, W]`` cores matrix.
        ``backlog_peak`` keeps the call's largest
        ``SimResult.backlog_peak``."""
        res = self._execute(*self.grid_arrays(points))
        _check_ok(res.ok, f"{type(self).__name__}({self.names!r}, "
                          f"{self.scheduler!r})", res.overflow)
        self.backlog_peak = int(np.max(res.backlog_peak))
        ms, xfer = np.asarray(res.makespan), np.asarray(res.transferred)
        if self._single_cluster:
            return ms[0], xfer[0]
        return ms, xfer


def simulate_dynamic_grid(graph, scheduler, n_workers, cores, points,
                          netmodel="maxmin", max_steps=None):
    """One-shot convenience wrapper around ``DynamicGridRunner``."""
    return DynamicGridRunner(graph, scheduler, n_workers, cores,
                             netmodel, max_steps)(points)
