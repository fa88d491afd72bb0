"""simlint JX1xx: static invariant checks on abstractly traced
simulator programs (DESIGN.md §7).

Every registered factory (``make_bucket_simulator``,
``make_bucket_dynamic_simulator``, ``make_bucket_scheduler``) is traced
with ``jax.make_jaxpr`` over ``specs.abstract_spec`` arguments — no
graph, no device, no XLA — and the resulting jaxprs are walked for the
compiled-program invariants the runtime parity suites can only probe
point-wise:

* JX101 — the trace itself fails (jax rejects a shape/dtype-unstable
  ``while_loop``/``scan`` carry at trace time) or a carry's body input
  and output avals disagree.
* JX102 — a carry leaf is *weak-typed*: a Python scalar constant was
  baked into loop state.  It traces today, but any strong-typed
  rewrite of one branch flips the carry signature and silently splits
  the compile group.
* JX103 — a float64/complex128 aval anywhere: the simulator contract
  is float32 end to end (f32 time granularity in ``sim.body``).
* JX104 — a declared-traced argument leaf is *dead*: no equation reads
  it, i.e. the factory constant-folded it at build time.  This is the
  traced-cores-contract violation class (a cluster baked into the
  closure compiles per cluster instead of per W).  Deadness is judged
  against per-target required-live sets because some leaves are dead
  *by design* (``obj_valid`` in the static path, ``seed`` everywhere
  but ``random``, ``msd`` for static schedulers).
* JX105 — flow-slot pool bounds: every max-min slot-mode target must
  carry ``int32[S]``/``float32[S]`` slot state with
  ``S = DOWNLOAD_SLOTS * W`` in its event loop, and no ``float32[E]``
  per-edge carry may survive (that is the legacy O(E) state the pool
  replaced).
* JX106 — ready-frontier bounds (DESIGN.md §3): frontier targets must
  carry the ``int32[CT]`` task frontier (and, in slot mode, the
  ``int32[CF]`` flow-candidate frontier) with ``(CF, CT) =
  frontier_caps_for(shape, n_workers=W)``, and a frontier slot-mode loop may not
  carry *any* ``[E]``-shaped state — the frontier+slot combination is
  exactly the mode whose event loop owns no per-edge arrays.  Checked
  on a dedicated bucket shape where the derived caps collide with no
  other axis, so carry classification by shape cannot alias.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax

from .report import Finding
from ..core.vectorized.engine import make_sharded_rows_fn
from ..core.vectorized.sim import (DOWNLOAD_SLOTS, make_bucket_simulator,
                                   make_bucket_dynamic_simulator)
from ..core.vectorized.scheduling import (VEC_SCHEDULERS,
                                          make_bucket_scheduler)
from ..core.vectorized.specs import (_BSPEC_FIELDS, BucketedGraphSpec,
                                     abstract_spec, frontier_caps_for)
from ..launch.mesh import make_grid_mesh

_BAD_DTYPES = ("float64", "complex128")


@dataclasses.dataclass(frozen=True)
class Target:
    """One abstract-trace check target: a built factory plus the
    abstract arguments and its liveness/slot-pool contract."""
    name: str
    fn: object                  # the traced-callable the factory returned
    args: tuple                 # abstract leaves (ShapeDtypeStruct pytrees)
    argnames: tuple             # one name per entry of ``args``
    required_live: frozenset    # leaf names that must appear in an eqn
    slot_pool: int | None = None       # expected S for slot-mode targets
    n_edges: int | None = None         # bucket E (for the banned f32[E] carry)
    frontier_caps: tuple | None = None  # expected (CF, CT) for frontier mode


# ---------------------------------------------------------------- walking

def _param_jaxprs(val):
    """Jaxprs nested in one eqn param (ClosedJaxpr, Jaxpr, or lists of
    them — ``cond`` branches)."""
    if hasattr(val, "jaxpr") and hasattr(val.jaxpr, "eqns"):
        return [val.jaxpr]                      # ClosedJaxpr
    if hasattr(val, "eqns"):
        return [val]                            # bare Jaxpr
    if isinstance(val, (list, tuple)):
        out = []
        for x in val:
            out.extend(_param_jaxprs(x))
        return out
    return []


def walk_jaxprs(jaxpr, path="top"):
    """Yield ``(path, jaxpr)`` for a jaxpr and all nested sub-jaxprs."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    yield path, jaxpr
    for eqn in jaxpr.eqns:
        for key in sorted(eqn.params):
            for sub in _param_jaxprs(eqn.params[key]):
                yield from walk_jaxprs(
                    sub, f"{path}/{eqn.primitive.name}.{key}")


def iter_eqns(jaxpr):
    for path, j in walk_jaxprs(jaxpr):
        for eqn in j.eqns:
            yield path, eqn


def _loop_carries(eqn):
    """``[(body_in_var, body_out_var), ...]`` for while/scan eqns."""
    p = eqn.params
    if eqn.primitive.name == "while":
        body = _param_jaxprs(p["body_jaxpr"])[0]
        ins = body.invars[p["body_nconsts"]:]
        outs = body.outvars
    elif eqn.primitive.name == "scan":
        body = _param_jaxprs(p["jaxpr"])[0]
        nc, nk = p["num_consts"], p["num_carry"]
        ins = body.invars[nc:nc + nk]
        outs = body.outvars[:nk]
    else:
        return []
    return list(zip(ins, outs, strict=True))


def _aval_str(aval):
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    s = f"{dtype}[{','.join(str(d) for d in (shape or ()))}]"
    if getattr(aval, "weak_type", False):
        s += "{weak}"
    return s


# ----------------------------------------------------------------- checks

def check_target(target: Target):
    """All JX1xx findings for one target."""
    loc = f"jaxpr:{target.name}"
    try:
        closed = jax.make_jaxpr(target.fn)(*target.args)
    except Exception as e:                      # trace-time carry rejection
        return [Finding("JX101", loc,
                        f"abstract trace failed (unstable carry or "
                        f"invalid program): {type(e).__name__}: {e}")]
    findings = []

    # JX103: no f64/c128 avals anywhere
    seen_bad = set()
    for path, j in walk_jaxprs(closed):
        for v in (list(j.invars) + list(j.constvars)
                  + [o for eqn in j.eqns for o in eqn.outvars]):
            aval = getattr(v, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt in _BAD_DTYPES and (path, dt) not in seen_bad:
                seen_bad.add((path, dt))
                findings.append(Finding(
                    "JX103", loc,
                    f"{dt} aval {_aval_str(aval)} at {path} (the "
                    f"simulator contract is float32 end to end)"))

    # JX101/JX102: carry stability + weak-typed carry leaves
    for path, eqn in iter_eqns(closed):
        for i, (vin, vout) in enumerate(_loop_carries(eqn)):
            a_in, a_out = vin.aval, getattr(vout, "aval", None)
            if (i == 0 and eqn.primitive.name == "scan"
                    and getattr(a_in, "shape", None) == ()
                    and str(getattr(a_in, "dtype", "")) == "int32"):
                # fori_loop's own induction counter: jax emits it weak
                # (python-int bounds) in every program identically, so
                # it cannot split a compile group — not user state
                continue
            si, so = _aval_str(a_in), _aval_str(a_out)
            if (getattr(a_in, "shape", None) != getattr(a_out, "shape", 0)
                    or str(getattr(a_in, "dtype", "")) != str(
                        getattr(a_out, "dtype", ""))):
                findings.append(Finding(
                    "JX101", loc,
                    f"{eqn.primitive.name} carry slot {i} at {path} is "
                    f"unstable: body input {si} != body output {so}"))
            elif (getattr(a_in, "weak_type", False)
                    or getattr(a_out, "weak_type", False)):
                findings.append(Finding(
                    "JX102", loc,
                    f"weak-typed {eqn.primitive.name} carry slot {i} at "
                    f"{path} ({si} -> {so}): a Python scalar constant is "
                    f"baked into the loop state"))

    # JX104: required-live argument leaves must reach an equation
    names = leaf_names(target.argnames, target.args)
    jaxpr = closed.jaxpr
    if len(names) == len(jaxpr.invars):
        used = set()
        for _path, eqn in iter_eqns(closed):
            for v in eqn.invars:
                if not hasattr(v, "val"):       # skip Literals
                    used.add(v)
        used.update(v for v in jaxpr.outvars if not hasattr(v, "val"))
        for name, var in zip(names, jaxpr.invars, strict=True):
            if name in target.required_live and var not in used:
                findings.append(Finding(
                    "JX104", loc,
                    f"traced argument {name} ({_aval_str(var.aval)}) is "
                    f"dead in the jaxpr — its value was constant-folded "
                    f"at factory-build time (traced-cores contract)"))
    else:                                       # should not happen
        findings.append(Finding(
            "JX104", loc,
            f"cannot align {len(names)} argument leaves with "
            f"{len(jaxpr.invars)} jaxpr invars; liveness not checked"))

    # JX105: bounded slot pool in the event loop, no per-edge f32 carry
    if target.slot_pool is not None:
        S, E = target.slot_pool, target.n_edges
        pool_seen = False
        for path, eqn in iter_eqns(closed):
            if eqn.primitive.name != "while":
                continue
            shapes = set()
            for vin, _vout in _loop_carries(eqn):
                aval = vin.aval
                key = (str(getattr(aval, "dtype", "")),
                       tuple(getattr(aval, "shape", ())))
                shapes.add(key)
                if E and key == ("float32", (E,)):
                    findings.append(Finding(
                        "JX105", loc,
                        f"float32[{E}] per-edge carry at {path} in a "
                        f"slot-mode target — the O(E) state the "
                        f"flow-slot pool replaced"))
            if ({("int32", (S,)), ("float32", (S,))} <= shapes):
                pool_seen = True
        if not pool_seen:
            findings.append(Finding(
                "JX105", loc,
                f"no while carry holds the int32[{S}]/float32[{S}] "
                f"flow-slot pool (expected S = DOWNLOAD_SLOTS*W = {S})"))

    # JX106: bounded frontier lists present; frontier+slot loops carry
    # no [E]-shaped state at all
    if target.frontier_caps is not None:
        CF, CT = target.frontier_caps
        E = target.n_edges
        want = {("int32", (CT,))}
        if target.slot_pool is not None:
            want.add(("int32", (CF,)))
        found = set()
        for path, eqn in iter_eqns(closed):
            if eqn.primitive.name != "while":
                continue
            for vin, _vout in _loop_carries(eqn):
                aval = vin.aval
                key = (str(getattr(aval, "dtype", "")),
                       tuple(getattr(aval, "shape", ())))
                if key in want:
                    found.add(key)
                if (target.slot_pool is not None and E
                        and key[1] == (E,)):
                    findings.append(Finding(
                        "JX106", loc,
                        f"{_aval_str(aval)} per-edge carry at {path} in a "
                        f"frontier slot-mode target — the O(E) loop state "
                        f"the ready frontier replaced"))
        for dt, shp in sorted(want - found):
            findings.append(Finding(
                "JX106", loc,
                f"no while carry holds the {dt}[{shp[0]}] frontier list "
                f"(frontier_caps_for derived CF={CF}, CT={CT})"))
    return findings


def leaf_names(argnames, args):
    """One name per flattened leaf of ``args``, aligned with the
    top-level jaxpr invars (spec fields spelled out)."""
    names = []
    for an, a in zip(argnames, args, strict=True):
        if isinstance(a, BucketedGraphSpec):
            names.extend(f"{an}.{f}" for f in _BSPEC_FIELDS)
        else:
            leaves = jax.tree_util.tree_leaves(a)
            if len(leaves) == 1:
                names.append(an)
            else:
                names.extend(f"{an}[{i}]" for i in range(len(leaves)))
    return names


# ------------------------------------------------------------ the grid

_SPEC_LEAVES = frozenset(f"bspec.{f}" for f in _BSPEC_FIELDS)
# the static path never reads obj_valid (sizes of invalid objects are
# already zero in the padded spec); everything else must stay traced
_STATIC_SIM_LIVE = frozenset(
    (_SPEC_LEAVES - {"bspec.obj_valid"})
    | {"assignment", "priority", "bandwidth", "cores"})
_SCHED_SPEC_LIVE = frozenset({"bspec.producer", "bspec.edge_task",
                              "bspec.edge_obj", "bspec.edge_valid",
                              "bspec.cpus"})


def _dynamic_live(scheduler):
    live = set(_SPEC_LEAVES) | {"est_durations", "est_sizes",
                                "decision_delay", "bandwidth", "cores"}
    if scheduler == "greedy":
        live.add("msd")             # only the in-loop scheduler is gated
    if scheduler == "random":
        live.add("seed")            # the only seed-consuming scheduler
        live.discard("est_sizes")   # random ignores transfer estimates
    return frozenset(live)


def _scheduler_live(scheduler):
    live = set(_SCHED_SPEC_LIVE) | {"est_durations", "cores"}
    if scheduler == "random":
        live.add("seed")
    else:
        live |= {"est_sizes", "bandwidth"}
    if scheduler == "etf":
        live.add("bspec.n_inputs")
    return frozenset(live)


def default_targets(n_workers: int = 4, shape=(32, 64, 96)):
    """The survey-grid check targets: both simulator families over both
    netmodels, every registered scheduler, and the static scheduler
    bindings — all with late-bound (traced) cores.  The default bucket
    shape keeps T, O, E and S = DOWNLOAD_SLOTS*W pairwise distinct so
    shape-based carry classification (JX105) cannot alias axes."""
    W = n_workers
    T, O, E = shape
    S = W * DOWNLOAD_SLOTS
    sds = jax.ShapeDtypeStruct
    spec = abstract_spec(shape)
    f32, i32 = np.float32, np.int32
    scalar_f = sds((), f32)
    scalar_i = sds((), i32)
    cores = sds((W,), i32)
    targets = []

    for netmodel in ("maxmin", "simple"):
        run = make_bucket_simulator(W, None, netmodel, max_cores=4)
        targets.append(Target(
            name=f"make_bucket_simulator[{netmodel}]",
            fn=run,
            args=(spec, sds((T,), i32), sds((T,), f32), None, None,
                  scalar_f, cores),
            argnames=("bspec", "assignment", "priority", "durations",
                      "sizes", "bandwidth", "cores"),
            required_live=_STATIC_SIM_LIVE,
            slot_pool=S if netmodel == "maxmin" else None,
            n_edges=E))

    dyn_args = (spec, sds((T,), f32), sds((O,), f32), scalar_f, scalar_f,
                scalar_f, scalar_i, cores)
    dyn_names = ("bspec", "est_durations", "est_sizes", "msd",
                 "decision_delay", "bandwidth", "seed", "cores")
    for sched in sorted(VEC_SCHEDULERS):
        for netmodel in ("maxmin", "simple"):
            run = make_bucket_dynamic_simulator(W, None, sched, netmodel,
                                                max_cores=4)
            targets.append(Target(
                name=f"make_bucket_dynamic_simulator[{sched},{netmodel}]",
                fn=run, args=dyn_args, argnames=dyn_names,
                required_live=_dynamic_live(sched),
                slot_pool=S if netmodel == "maxmin" else None,
                n_edges=E))

    # frontier grid (JX106): traced again on a bucket shape where the
    # derived caps (CF=512, CT=320) are distinct from every other axis
    # (T=1280, O=192, E=2048, S=16, O*W=768), so [cap]-shaped carries
    # cannot alias [T]/[E] state.  The survey-grid targets above
    # exercise the frontier path too (it is the default), but at
    # (32, 64, 96) the caps equal T and E and the bound is unfalsifiable.
    fr_shape = (1280, 192, 2048)
    Tf, Of, Ef = fr_shape
    fr_spec = abstract_spec(fr_shape)
    fr_caps = frontier_caps_for(fr_shape, n_workers=W)
    for netmodel in ("maxmin", "simple"):
        run = make_bucket_simulator(W, None, netmodel, max_cores=4)
        targets.append(Target(
            name=f"make_bucket_simulator[{netmodel},frontier@T{Tf}]",
            fn=run,
            args=(fr_spec, sds((Tf,), i32), sds((Tf,), f32), None, None,
                  scalar_f, cores),
            argnames=("bspec", "assignment", "priority", "durations",
                      "sizes", "bandwidth", "cores"),
            required_live=_STATIC_SIM_LIVE,
            slot_pool=S if netmodel == "maxmin" else None,
            n_edges=Ef, frontier_caps=fr_caps))
    fr_dyn_args = (fr_spec, sds((Tf,), f32), sds((Of,), f32), scalar_f,
                   scalar_f, scalar_f, scalar_i, cores)
    for sched, netmodel in (("blevel", "maxmin"), ("greedy", "maxmin"),
                            ("blevel", "simple")):
        run = make_bucket_dynamic_simulator(W, None, sched, netmodel,
                                            max_cores=4)
        targets.append(Target(
            name=(f"make_bucket_dynamic_simulator"
                  f"[{sched},{netmodel},frontier@T{Tf}]"),
            fn=run, args=fr_dyn_args, argnames=dyn_names,
            required_live=_dynamic_live(sched),
            slot_pool=S if netmodel == "maxmin" else None,
            n_edges=Ef, frontier_caps=fr_caps))

    # the frontier=False escape hatch must keep tracing with the PR-4
    # carry contract (slot pool present, no f32[E] in slot mode)
    run = make_bucket_simulator(W, None, "maxmin", max_cores=4,
                                frontier=False)
    targets.append(Target(
        name="make_bucket_simulator[maxmin,frontier=off]",
        fn=run,
        args=(spec, sds((T,), i32), sds((T,), f32), None, None,
              scalar_f, cores),
        argnames=("bspec", "assignment", "priority", "durations",
                  "sizes", "bandwidth", "cores"),
        required_live=_STATIC_SIM_LIVE, slot_pool=S, n_edges=E))
    run = make_bucket_dynamic_simulator(W, None, "blevel", "maxmin",
                                        max_cores=4, frontier=False)
    targets.append(Target(
        name="make_bucket_dynamic_simulator[blevel,maxmin,frontier=off]",
        fn=run, args=dyn_args, argnames=dyn_names,
        required_live=_dynamic_live("blevel"), slot_pool=S, n_edges=E))

    # the sharded engine program (engine.py, DESIGN.md §9): the same
    # dynamic simulator vmapped over clusters x rows under shard_map on
    # a 1-device "grid" mesh, traced with batched (rows-leading) avals.
    # The carry/dtype contracts (JX101-103) must survive the batching;
    # slot-pool and frontier classification (JX105/106) stay off
    # because vmap prepends the rows axis to every while carry, so the
    # [S]/[cap] shape keys cannot match by construction.  Liveness
    # (JX104) is vacuous across the shard_map eqn boundary — every
    # operand feeds the shard_map call — so required_live is empty
    # rather than pretending coverage the walk cannot falsify.
    G, K = 2, 2
    def rows(l):
        return sds((G,) + tuple(l.shape), l.dtype)
    eng_run = make_bucket_dynamic_simulator(W, None, "blevel", "maxmin",
                                            max_cores=4)
    targets.append(Target(
        name="sharded_engine[blevel,maxmin,grid@1]",
        fn=make_sharded_rows_fn(eng_run, make_grid_mesh(1)),
        args=(jax.tree_util.tree_map(rows, spec), rows(sds((T,), f32)),
              rows(sds((O,), f32)), rows(scalar_f), rows(scalar_f),
              rows(scalar_f), rows(scalar_i), sds((K, W), i32)),
        argnames=dyn_names,
        required_live=frozenset()))

    sched_args = (spec, sds((T,), f32), sds((O,), f32), scalar_f,
                  scalar_i, cores)
    sched_names = ("bspec", "est_durations", "est_sizes", "bandwidth",
                   "seed", "cores")
    for sched in sorted(k for k, v in VEC_SCHEDULERS.items()
                        if v == "static"):
        fn = make_bucket_scheduler(W, None, sched, max_cores=4)
        targets.append(Target(
            name=f"make_bucket_scheduler[{sched}]",
            fn=fn, args=sched_args, argnames=sched_names,
            required_live=_scheduler_live(sched)))
    return targets


def check_all(targets=None, n_workers: int = 4, shape=(32, 64, 96)):
    """Run every JX1xx check over the target grid; returns findings."""
    if targets is None:
        targets = default_targets(n_workers, shape)
    findings = []
    for t in targets:
        findings.extend(check_target(t))
    return findings
