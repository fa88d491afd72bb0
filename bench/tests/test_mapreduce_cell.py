"""The MapReduce cell: its graph is the program's recipe instance, and
its backlog share reads nothing on a program without the scope."""
import sys
import types

from bench import cells, graphs, phases, trace

from .test_phases import RECORDED, context
from .test_reference import flat

CELL = "mapreduce-greedy-maxmin"


def test_graph_matches_the_program_recipe():
    from repro.core.graphs import make_graph as program_make_graph

    from bench.program import to_program_graph

    ours = graphs.load("mapreduce-65-s0")
    theirs = program_make_graph("mapreduce-65-s0", seed=0)
    assert ours.name == theirs.name
    assert flat(ours) == flat(theirs)
    assert flat(to_program_graph(ours)) == flat(theirs)
    assert (len(ours.tasks), len(ours.objects)) == (65, 1056)


def test_backlog_share_is_silent_without_the_program(monkeypatch):
    """A program with no ``SIM_PHASES`` at all: the trace is not
    opened."""
    monkeypatch.setitem(sys.modules, "repro.core.vectorized",
                        types.ModuleType("repro.core.vectorized"))

    def opened(*args):
        raise AssertionError("the trace was opened")
    monkeypatch.setattr(phases, "newest_trace", opened)
    ctx = context(CELL, trace.reduce(trace.read_events(RECORDED)))
    assert cells.Cell.load(CELL).reader("phase_backlog_pct")(ctx) is None


def test_backlog_share_is_silent_where_the_loop_has_no_backlog(
        monkeypatch):
    """The parent's four phases, without ``sim.backlog``: the other
    shares read, this one gives nothing."""
    four = ("sim.schedule", "sim.ready", "sim.rates", "sim.advance")
    monkeypatch.setattr(phases, "_program",
                        lambda name: four if name == "SIM_PHASES" else None)
    monkeypatch.setattr(phases, "newest_trace", lambda d: "trace.xplane.pb")
    monkeypatch.setattr(phases.os.path, "getmtime", lambda p: 0.0)
    monkeypatch.setattr(phases, "scope_paths",
                        lambda path, ph: {"fusion.1": "while/body/sim.ready"})
    summary = types.SimpleNamespace(ops={"%fusion.1": (1, 10, 10)})
    ctx = context(CELL, summary)
    cell = cells.Cell.load(CELL)
    assert cell.reader("phase_ready_pct")(ctx) == 100.0
    assert cell.reader("phase_backlog_pct")(ctx) is None


def test_the_backlog_share_is_read_in_the_cell_alone():
    for name in ("t160-blevel-maxmin", "t512-greedy-simple",
                 "t160-blevel-maxmin-x4", CELL):
        listed = {m["name"] for m in cells.Cell.load(name).per_layer}
        assert ("phase_backlog_pct" in listed) == (name == CELL), name


def test_the_waterfill_shares_are_read_in_the_cell():
    """Greedy's placer and the waterfill share this cell's loop, so the
    kernel's share and roofline are read here as in the T160 cell."""
    for name in ("t160-blevel-maxmin", "t512-greedy-simple", CELL):
        listed = {m["name"] for m in cells.Cell.load(name).per_layer}
        for metric in ("waterfill_pct", "waterfill_roofline"):
            assert (metric in listed) == (name != "t512-greedy-simple"), \
                (name, metric)
