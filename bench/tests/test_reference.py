"""The benchmark's graphs and reference stay what they were taken from,
and the control of the correctness check comes out not correct."""
import pytest

from bench import cells, check, control, graphs, reference
from bench import traffic as gen

GRAPHS = ["merge_triplets", "fastcrossv", "crossv", "sipht", "montage",
          "cybershake", "fork1", "size_stairs", "crossvx"]
SEED = 3000000017


def flat(g):
    return ([(t.duration, t.cpus, t.expected_duration, t.name,
              [o.id for o in t.inputs], [o.id for o in t.outputs])
             for t in g.tasks],
            [(o.size, o.expected_size, o.parent.id,
              [c.id for c in o.consumers]) for o in g.objects])


@pytest.mark.parametrize("name", GRAPHS)
def test_graphs_match_the_program(name):
    """Each data file is the program's generator at instance seed 0."""
    from repro.core.graphs import make_graph as program_make_graph

    from bench.program import to_program_graph

    ours = graphs.load(name)
    theirs = program_make_graph(name, seed=0)
    assert ours.name == theirs.name
    assert flat(ours) == flat(theirs)
    assert flat(to_program_graph(ours)) == flat(theirs)


@pytest.mark.parametrize("sched,netmodel", [("blevel", "maxmin"),
                                            ("greedy", "simple")])
def test_reference_matches_the_program_reference(sched, netmodel):
    from repro.core import Simulator, make_scheduler, parse_cluster

    g = graphs.load("montage")
    point = dict(bandwidth=32 * 2**20, imode="user", msd=0.1,
                 decision_delay=0.05)
    twin = {"blevel": "blevel-det", "greedy": "greedy"}[sched]
    rep = Simulator(make_graph_program("montage"), parse_cluster("32x4"),
                    make_scheduler(twin), netmodel=netmodel,
                    bandwidth=point["bandwidth"], imode="user", msd=0.1,
                    decision_delay=0.05).run()
    assert reference.simulate(g, "32x4", sched, netmodel, point) == \
        (rep.makespan, rep.transferred_bytes)


def make_graph_program(name):
    from repro.core.graphs import make_graph as program_make_graph

    return program_make_graph(name, seed=0)


def test_control_is_not_correct():
    """The bfloat16 control over a small sample of cell 1's grid."""
    cell = cells.Cell.load("t160-blevel-maxmin")
    cell.config["graphs"] = ["merge_triplets", "montage"]
    cell.traffic["check"]["sample"] = 12
    got = control.reading(cell, SEED)
    assert not got["correct"]
    assert got["compared"]["gap_worst"]["value"] > \
        3 * got["compared"]["gap_worst"]["limit"]


def test_a_sound_answer_is_correct():
    cell = cells.Cell.load("t512-greedy-simple")
    cell.config["graphs"] = ["sipht"]
    config, traffic = cell.config, cell.traffic
    keys = control.all_keys(config)[:6]
    refs = check.reference_answers(keys, gen.graphs(config), config, traffic,
                                   gen.grid_points(config))
    answers = {k: (ms * (1 + 1e-7), xf, True, 1)
               for k, (ms, xf) in refs.items()}
    numbers = check.compare(answers, refs, 0, 0)
    assert check.verdict(numbers, check.limits(traffic))[0]


def test_one_answer_off_is_not_correct():
    """One answer of twelve a thousandth off is a wrong answer."""
    traffic = cells.Cell.load("t160-blevel-maxmin").traffic
    refs = {(0, 0, p): (10.0 + p, 1e9) for p in range(12)}
    answers = {key: (ms * (1.001 if key[2] == 5 else 1.0), xf, True, 1)
               for key, (ms, xf) in refs.items()}
    numbers = check.compare(answers, refs, 0, 0)
    correct, compared = check.verdict(numbers, check.limits(traffic))
    assert compared["gap_worst"]["value"] > compared["gap_worst"]["limit"]
    assert not correct


def test_download_ties_go_by_the_smallest_arc():
    """fastcrossv holds downloads of equal priority (inputs of one task);
    the reference starts them in order of the smallest (task, input)
    arc, as the program does, and agrees with the program's grid answer
    where the order of first sight reads a hundredth off."""
    from bench import program

    cell = cells.Cell.load("t160-blevel-maxmin")
    config, traffic = cell.config, cell.traffic
    config["graphs"] = ["fastcrossv"]
    points = gen.grid_points(config)
    p = 8                               # 128 MiB/s, user estimates, MSD 0
    g = gen.graphs(config)
    prog = program.Program(config, traffic, g, None)
    res = prog.readback(prog.dispatch(prog.prep([points[p]])), 1)
    got = (float(res.makespan[1, 0, 0]), float(res.transferred[1, 0, 0]),
           bool(res.ok[1, 0, 0]))
    ours = reference.simulate(g[0], "32x16", "blevel", "maxmin", points[p])
    assert check.gap(got, ours) < 1e-5
