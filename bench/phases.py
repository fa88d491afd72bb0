"""The program's phases: the event loop's on the device, set-up's on the
host.  The helper of the ``phase_*_pct`` and ``setup_*_s`` readers.

The program names the phases of its loop body with ``jax.named_scope``
and lists their names as ``SIM_PHASES`` in ``repro.core.vectorized``.
The compiler keeps the scope path in each HLO instruction's ``op_name``,
and the profiler keeps the optimized HLO of the programs it ran in the
``/host:metadata`` plane of the window's ``.xplane.pb``.  Two steps:

* ``scope_paths`` reads that plane alone (the device planes, which hold
  every event, are skipped unread) and gives each HLO instruction a
  scope path: its own ``op_name`` where that names a phase; else, for a
  fusion, a path of the phase most of its fused instructions name; else
  the path of the nearest instruction its result flows into, or failing
  that, comes from.  The compiler's own rewrites (a scatter expanded
  into a sort, a fusion inside a fusion) carry no phase of their own.
* ``shares`` is the pure reduction: from op names, their scope paths
  and ``Summary.ops`` to each phase's percentage of the device's self
  time, with ``unscoped`` for ops whose path names no phase.  The
  shares add to 100.

The file is read once per run, whatever the number of readers.  Set-up
is the program's own count, ``setup_seconds()`` (``setup_split``).  A
program without ``SIM_PHASES`` or ``setup_seconds`` gives no number,
and its trace is not opened.
"""
from __future__ import annotations

import collections
import glob
import importlib
import os
import re
import sys
import time
import typing

from bench import cells

UNSCOPED = "unscoped"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
# where run.py writes a traced window, under the checkout
TRACE_DIR = os.path.join(".bench_trace", "{workload}")
# the control flow ops hold other ops and do no work of their own but
# the loop's bookkeeping: never named by what they hold
CONTROL_FLOW = frozenset({"while", "conditional", "call"})

_CACHE = {}          # (trace file, mtime) -> {instruction: scope path}


def _program(name: str):
    """``name`` of the program's ``repro.core.vectorized``, or ``None``
    when the program has no such name or no such module."""
    try:
        mod = importlib.import_module("repro.core.vectorized")
    except ImportError:
        return None
    return getattr(mod, name, None)


def setup_split(ctx):
    """``{"trace", "compile", "host": s}`` of set-up as the program
    counts it (``setup_seconds()``), and ``"other"``: ``setup_s`` less
    those; ``None`` when the program keeps no such count."""
    seconds = _program("setup_seconds")
    if seconds is None:
        return None
    split = {k: float(seconds().get(k, 0.0))
             for k in ("trace", "compile", "host")}
    split["other"] = ctx.setup["setup_s"] - sum(split.values())
    return split


def phase_shares(ctx):
    """``{phase: %}`` of the traced window's device self time (the
    program's phases and ``unscoped``), or ``None`` when the program
    declares no phases or the window holds no device op."""
    phases = _program("SIM_PHASES")
    if not phases or ctx.summary is None or not ctx.summary.ops:
        return None
    path = newest_trace(os.path.join(cells.ROOT,
                                     TRACE_DIR.format(workload=ctx.cell)))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        t0 = time.perf_counter()
        _CACHE.clear()
        _CACHE[key] = scope_paths(path, phases)
        print(f"phases: {len(_CACHE[key])} HLO instructions read from the "
              f"trace in {time.perf_counter() - t0:.3f} s", file=sys.stderr,
              flush=True)
    return shares(_CACHE[key], ctx.summary.ops, phases)


def shares(paths, ops, phases):
    """``{phase: %}`` of the summed self time of ``ops`` (``op name ->
    (calls, ns, self ns)``, as ``trace.Summary.ops``), each op charged to
    the innermost of ``phases`` its scope path names (``paths``: op name
    without the leading ``%`` -> scope path), else to ``unscoped``;
    ``None`` when the ops hold no time."""
    total = sum(own for _, _, own in ops.values())
    if total <= 0:
        return None
    out = dict.fromkeys((*phases, UNSCOPED), 0.0)
    for op, (_, _, own) in ops.items():
        out[phase_of(paths.get(op.lstrip("%"), ""), phases)
            or UNSCOPED] += own
    return {k: 100.0 * v / total for k, v in out.items()}


def phase_of(path: str, phases):
    """The innermost of ``phases`` that the scope path ``path`` names,
    as a component or inside a transform's name (``vmap(sim.schedule)``
    for a scope entered outside a ``vmap``), else ``None``."""
    hits = [t for t in re.split(r"[/()]", path) if t in phases]
    return hits[-1] if hits else None


def newest_trace(trace_dir: str):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


# ------------------------------------------------ reading the .xplane.pb

def scope_paths(path: str, phases) -> dict:
    """``{HLO instruction name: scope path}`` over every HLO module in
    the metadata plane of the ``.xplane.pb`` at ``path``; the path names
    one of ``phases`` wherever the instruction can be charged to one.
    Where two modules name an instruction alike, a path that names a
    phase wins."""
    out = {}
    for module in _hlo_modules(path):
        for name, p in assign_paths(_parse_module(module), phases).items():
            if name not in out or (phase_of(p, phases)
                                   and not phase_of(out[name], phases)):
                out[name] = p
    return out


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, lo=0, hi=None):
    """``(field number, value)`` of a protobuf message in ``buf[lo:hi]``:
    an int for a varint, a ``(start, end)`` span for a length-delimited
    field; fixed-width fields are skipped."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif kind == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode()


def _metadata_plane(path: str):
    """The bytes of the ``XPlane`` named ``/host:metadata`` (an
    ``XSpace``'s planes are field 1, a plane's name field 2), read by
    seeking past every other plane."""
    with open(path, "rb") as f:
        size = os.fstat(f.fileno()).st_size
        while f.tell() < size:
            head = f.read(20)
            key, i = _varint(head, 0)
            n, i = _varint(head, i)
            start = f.tell() - len(head) + i
            f.seek(start)
            if key == (1 << 3 | 2):
                peek = f.read(min(n, 256))
                for field, value in _fields(peek, 0, len(peek)):
                    if field == 2:
                        if _text(peek, value) == METADATA_PLANE:
                            f.seek(start)
                            return memoryview(f.read(n))
                        break
            f.seek(start + n)
    return None


def _hlo_modules(path: str):
    """Each ``HloModuleProto`` the metadata plane holds (its event
    metadata carry ``HloProto``s as the bytes of the ``Hlo Proto``
    stat)."""
    plane = _metadata_plane(path)
    if plane is None:
        return
    stat_ids, events = set(), []
    for field, value in _fields(plane):
        if field in (4, 5):          # event_metadata, stat_metadata maps
            entry = dict(_fields(plane, *value))
            if 2 not in entry:
                continue
            if field == 5:
                meta = dict(_fields(plane, *entry[2]))
                if _text(plane, meta.get(2, (0, 0))) == HLO_PROTO_STAT:
                    stat_ids.add(meta.get(1, entry.get(1)))
            else:
                events.append(entry[2])
    for span in events:
        for field, value in _fields(plane, *span):
            if field != 5:           # XEventMetadata.stats
                continue
            stat = dict(_fields(plane, *value))
            if stat.get(1) in stat_ids and 6 in stat:
                for f2, module in _fields(plane, *stat[6]):
                    if f2 == 1:      # HloProto.hlo_module
                        yield plane[module[0]:module[1]]


class Instr(typing.NamedTuple):
    name: str
    opcode: str
    op_name: str
    id: int
    operands: list        # instruction ids
    called: list          # computation ids


def _parse_module(module):
    """``{computation id: [Instr]}`` of an ``HloModuleProto``."""
    comps = {}
    for field, value in _fields(module):
        if field != 3:                           # computations
            continue
        cid, instrs = None, []
        for f2, v2 in _fields(module, *value):
            if f2 == 5:
                cid = v2
            elif f2 == 2:                        # instructions
                name = opcode = op_name = ""
                iid, operands, called = None, [], []
                for f3, v3 in _fields(module, *v2):
                    if f3 == 1:
                        name = _text(module, v3)
                    elif f3 == 2:
                        opcode = _text(module, v3)
                    elif f3 == 7:                # OpMetadata
                        for f4, v4 in _fields(module, *v3):
                            if f4 == 2:
                                op_name = _text(module, v4)
                    elif f3 == 35:
                        iid = v3
                    elif f3 in (36, 38):         # operand / called ids
                        ids = _packed(module, v3)
                        (operands if f3 == 36 else called).extend(ids)
                instrs.append(Instr(name, opcode, op_name, iid, operands,
                                    called))
        comps[cid] = instrs
    return comps


def _packed(buf, value):
    if isinstance(value, int):
        return [value]
    out, i = [], value[0]
    while i < value[1]:
        v, i = _varint(buf, i)
        out.append(v)
    return out


def assign_paths(comps, phases) -> dict:
    """``{instruction name: scope path}`` of a module's computations
    (``{computation id: [Instr]}``): the rules of ``scope_paths``."""

    def fused_path(called, seen):
        """A path of the phase most instructions of the called
        computations (nested fusions included) are scoped to."""
        votes, first = collections.Counter(), {}
        for cid in called:
            if cid in seen:
                continue
            seen.add(cid)
            for ins in comps.get(cid, ()):
                path = ins.op_name if phase_of(ins.op_name, phases) else (
                    ins.opcode == "fusion" and fused_path(ins.called, seen))
                if path:
                    phase = phase_of(path, phases)
                    votes[phase] += 1
                    first.setdefault(phase, path)
        return first[votes.most_common(1)[0][0]] if votes else ""

    own = {}                 # instruction -> a path that names a phase
    for instrs in comps.values():
        for ins in instrs:
            if phase_of(ins.op_name, phases):
                own[ins.name] = ins.op_name
            elif ins.opcode == "fusion":
                own[ins.name] = fused_path(ins.called, set())
            else:
                own[ins.name] = ""
    paths = {}
    for instrs in comps.values():
        by_id = {ins.id: ins for ins in instrs}
        users = collections.defaultdict(list)
        for ins in instrs:
            for o in ins.operands:
                users[o].append(ins.id)
        operands = {ins.id: ins.operands for ins in instrs}
        for ins in instrs:
            paths[ins.name] = own[ins.name] or (
                ins.opcode not in CONTROL_FLOW
                and (_nearest(ins.id, users, by_id, own)
                     or _nearest(ins.id, operands, by_id, own))) \
                or ins.op_name
    return paths


def _nearest(start, edges, by_id, own):
    """The path of the nearest instruction reached from ``start`` along
    ``edges`` (breadth first, never through control flow) whose own path
    names a phase."""
    seen, frontier = {start}, [start]
    while frontier:
        nxt = []
        for iid in frontier:
            for other in edges.get(iid, ()):
                if other in seen or other not in by_id:
                    continue
                seen.add(other)
                ins = by_id[other]
                if ins.opcode in CONTROL_FLOW:
                    continue
                if own[ins.name]:
                    return own[ins.name]
                nxt.append(other)
        frontier = nxt
    return ""
