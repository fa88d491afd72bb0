"""The phase and set-up readers: silent on a program that lacks what they
read, the reading of a trace's HLO, the rules that charge an op to a
phase, and the reduction to shares on a small split recorded on a v5e."""
import json
import os
import re
import sys
import time
import types

import pytest

from bench import cells, phases, trace

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "data")
RECORDED = os.path.join(DATA, "v5e_t160_blevel_maxmin.json.gz")
PHASES = ("sim.schedule", "sim.ready", "sim.rates", "sim.advance")
PHASE_METRICS = ("phase_schedule_pct", "phase_ready_pct", "phase_rates_pct",
                 "phase_advance_pct", "phase_unscoped_pct")
SETUP_METRICS = ("setup_trace_s", "setup_compile_s", "setup_host_s",
                 "setup_other_s")


def context(cell, summary, setup_s=30.0):
    return types.SimpleNamespace(
        requests=[], config={}, traffic={}, cell=cell,
        setup={"setup_s": setup_s, "exec_ready_s": 15.0},
        device_kind="TPU v5 lite", summary=summary)


@pytest.fixture
def parent_program(monkeypatch):
    """A program like the parent's: its ``repro.core.vectorized`` has no
    ``SIM_PHASES`` and no ``setup_seconds``, and its trace has no scopes.
    Opening the trace would be a fault."""
    monkeypatch.setitem(sys.modules, "repro.core.vectorized",
                        types.ModuleType("repro.core.vectorized"))

    def opened(*args):
        raise AssertionError("the trace was opened")
    monkeypatch.setattr(phases, "newest_trace", opened)
    monkeypatch.setattr(phases, "scope_paths", opened)


@pytest.mark.parametrize("cell", ["t160-blevel-maxmin", "t512-greedy-simple",
                                  "t160-blevel-maxmin-x4"])
def test_every_new_reader_is_silent_on_the_parent(parent_program, cell):
    ctx = context(cell, trace.reduce(trace.read_events(RECORDED)))
    readers = cells.Cell.load(cell)
    for name in PHASE_METRICS + SETUP_METRICS:
        assert readers.reader(name)(ctx) is None, name


def test_the_new_metrics_are_read_in_every_cell():
    for name in ("t160-blevel-maxmin", "t512-greedy-simple",
                 "t160-blevel-maxmin-x4"):
        listed = {m["name"] for m in cells.Cell.load(name).per_layer}
        assert set(PHASE_METRICS + SETUP_METRICS) <= listed, name


def test_no_trace_and_no_ops_give_no_shares(monkeypatch, tmp_path):
    monkeypatch.setattr(cells, "ROOT", str(tmp_path))
    s = trace.reduce(trace.read_events(RECORDED))
    assert phases.phase_shares(context("t160-blevel-maxmin", s)) is None
    assert phases.phase_shares(context("t160-blevel-maxmin", None)) is None


def test_setup_split_reads_the_program_count():
    ctx = context("t160-blevel-maxmin", None, setup_s=1e6)
    split = phases.setup_split(ctx)
    from repro.core.vectorized import setup_seconds

    now = setup_seconds()
    assert split["trace"] <= now["trace"] and split["host"] <= now["host"]
    assert split["other"] == pytest.approx(
        1e6 - split["trace"] - split["compile"] - split["host"])


def test_scope_paths_reads_the_hlo_of_a_trace(tmp_path):
    """A trace taken on the CPU holds the HLO of the programs it ran in
    its metadata plane, as a chip's does: every phase is found, and the
    ops of a phase inside a ``while`` body are charged to it."""
    import jax
    import jax.numpy as jnp

    def step(c):
        i, x, hist = c
        with jax.named_scope(PHASES[0]):
            order = jnp.argsort(-x)
        with jax.named_scope(PHASES[1]):
            hist = hist.at[order[:4]].add(1)
        with jax.named_scope(PHASES[2]):
            rate = jnp.cumsum(x) / (1.0 + i)
        with jax.named_scope(PHASES[3]):
            x = x - 0.1 * rate
        return i + 1, x, hist

    f = jax.jit(lambda x: jax.lax.while_loop(
        lambda c: c[0] < 5, step, (0, x, jnp.zeros(16, jnp.int32))))
    x = jnp.linspace(0.0, 1.0, 16)
    jax.block_until_ready(f(x))
    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(f(x))
    jax.profiler.stop_trace()
    path = phases.newest_trace(str(tmp_path))
    t0 = time.perf_counter()
    paths = phases.scope_paths(path, PHASES)
    assert time.perf_counter() - t0 < 5
    found = {phases.phase_of(p, PHASES) for p in paths.values()}
    assert set(PHASES) <= found
    # the file gives each instruction the op_name the compiled text shows
    named = re.findall(r'%(\S+) = [^\n]*metadata=\{op_name="([^"]*)"',
                       f.lower(x).compile().as_text())
    scoped = [(n, p) for n, p in named if phases.phase_of(p, PHASES)]
    assert scoped
    for name, p in scoped:
        assert paths[name] == p, name


def test_phase_of_finds_a_scope_inside_a_transform():
    assert phases.phase_of("jit(run)/vmap(vmap(sim.schedule))/while/body/"
                           "closed_call/gather", PHASES) == "sim.schedule"
    assert phases.phase_of("jit(run)/while/body/sim.ready/jit(searchsorted)"
                           "/gather", PHASES) == "sim.ready"
    assert phases.phase_of("jit(run)/while/cond/reduce_and", PHASES) is None
    assert phases.phase_of("", PHASES) is None
    assert phases.phase_of("sim.readyish/add", PHASES) is None


def test_ops_the_compiler_made_are_charged_by_what_they_feed():
    """Rules, on a made-up module: an op's own path where it names a
    phase; a fusion's by what most of its fused ops name; else the
    nearest op its result flows into, then the nearest it comes from;
    control flow and ops with no named neighbour stay unscoped."""
    Instr = phases.Instr
    body = "jit(run)/while/body/"
    comps = {
        1: [Instr("param", "parameter", "", 10, [], []),
            Instr("sort.1", "sort", "", 11, [10], [9]),
            Instr("scatter.2", "scatter", body + "sim.advance/scatter-add",
                  12, [11], []),
            Instr("fusion.3", "fusion", "", 13, [10], [2]),
            Instr("copy.4", "copy", "", 14, [13], []),
            Instr("cumsum.5", "reduce-window", "reduce_window_sum", 15, [],
                  []),
            Instr("add.6", "add", body + "sim.rates/add", 16, [15], []),
            Instr("while.7", "while", "jit(run)/while", 17, [], [3])],
        2: [Instr("a", "add", body + "sim.ready/add", 20, [], []),
            Instr("b", "and", body + "sim.ready/and", 21, [20], []),
            Instr("c", "or", body + "sim.schedule/or", 22, [21], [])],
        3: [Instr("cond", "and", "jit(run)/while/cond/and", 30, [], [])],
        9: [Instr("lt", "compare", "", 90, [], [])],
    }
    paths = phases.assign_paths(comps, PHASES)

    def phase(name):
        return phases.phase_of(paths[name], PHASES)
    assert phase("scatter.2") == "sim.advance"
    assert phase("sort.1") == "sim.advance"       # flows into the scatter
    assert phase("fusion.3") == "sim.ready"       # two of its three ops
    assert phase("copy.4") == "sim.ready"         # comes from the fusion
    assert phase("cumsum.5") == "sim.rates"       # flows into add.6
    assert phase("while.7") is None and phase("cond") is None
    assert phase("lt") is None


def test_shares_add_to_100_and_skip_the_percent_sign():
    paths = {"fusion.1": "x/sim.ready/gather", "fusion.2": "x/sim.rates/y",
             "copy.3": "x/while"}
    ops = {"%fusion.1": (2, 50.0, 30.0), "%fusion.2": (1, 10.0, 10.0),
           "%copy.3": (1, 10.0, 10.0), "%other": (1, 10.0, 0.0)}
    got = phases.shares(paths, ops, PHASES)
    assert got == {"sim.schedule": 0.0, "sim.ready": 60.0,
                   "sim.rates": 20.0, "sim.advance": 0.0, "unscoped": 20.0}
    assert phases.shares(paths, {"%x": (1, 0.0, 0.0)}, PHASES) is None


@pytest.mark.parametrize("parent", [True, False])
def test_a_traced_rehearsal_leaves_out_what_the_program_lacks(
        tmp_path, monkeypatch, interpret_waterfill, parent):
    """The harness with this benchmark's files on the CPU: a program
    without the names leaves every new metric out of the line and the
    run correct; with them, the set-up split is in the line and adds up.
    No device op runs here, so the phase shares stay out either way."""
    from bench.tests.test_rehearsal import rehearse
    import repro.core.vectorized as program

    from repro.core.vectorized import engine

    if parent:
        monkeypatch.delattr(program, "SIM_PHASES")
        monkeypatch.delattr(program, "setup_seconds")
    # the count is the process's; this process ran other tests before
    monkeypatch.setattr(engine, "_SETUP_SECONDS",
                        dict.fromkeys(engine.SETUP_PHASES, 0.0))
    out = rehearse(cells.Cell.load("t160-blevel-maxmin"), tmp_path,
                   graphs=["merge_triplets"], traced=True)
    assert out["correct"], out["compared"]
    got = set(out["metrics"])
    assert {"host_ms_per_request", "exec_ready_s"} <= got
    assert not got & set(PHASE_METRICS)
    if parent:
        assert not got & set(SETUP_METRICS)
    else:
        assert set(SETUP_METRICS) <= got
        assert out["metrics"]["setup_other_s"]["value"] >= 0


def test_recorded_chip_split():
    """Ten ops of a `t160-blevel-maxmin` window on one v5e, with the scope
    paths the trace's HLO gave them: the two of most self time in each
    phase and of the unscoped.  The shares, worked out by hand from the
    self times (ns) below, over their sum 1,561,794,625."""
    with open(os.path.join(DATA, "v5e_t160_phases.json")) as f:
        rec = json.load(f)
    ops = {op: tuple(v) for op, v in rec["ops"].items()}
    got = phases.shares(rec["paths"], ops, PHASES)
    total = 1_561_794_625
    assert sum(own for _, _, own in ops.values()) == total
    want = {
        "sim.schedule": 16_154_635 + 4_275_881,        # the static blevel
        "sim.ready": 821_166_887 + 281_378_106,        # frontier appends
        "sim.rates": 391_594_031 + 42_500,             # the waterfill
        "sim.advance": 29_299_408 + 16_442_347,
        "unscoped": 980_536 + 460_294,                 # while, its cond
    }
    assert got == pytest.approx({k: 100 * v / total for k, v in want.items()},
                                rel=1e-12)
    assert sum(got.values()) == pytest.approx(100, abs=1e-6)
