"""Workload subsystem (repro.workloads): recipe generators, dataset
manifests, adaptive bucket edges, the pad_specs overflow policy and the
registry/seed plumbing (DESIGN.md §6)."""
import numpy as np
import pytest

from repro.core.graphs import (DATASETS, RECIPE_INSTANCES, SURVEY_GRAPHS,
                               encode_graph_batch, make_graph)
from repro.core.vectorized import encode_graph, pad_specs, t_bucket
from repro.workloads import (MANIFESTS, PEGASUS_EQUIVALENT, RECIPE_FAMILIES,
                             Recipe, WFCOMMONS_MINI, build_dataset,
                             compute_bucket_edges, compute_w_buckets,
                             default_manifest, get_manifest,
                             instance_rng_seed, parse_instance, sample_dist)

# ------------------------------------------------------------- recipes


@pytest.mark.parametrize("family,n", sorted(PEGASUS_EQUIVALENT.items()))
def test_recipes_reproduce_fixed_generator_structure(family, n):
    """At the PEGASUS_EQUIVALENT counts the recipes derive exactly the
    fixed generators' structural parameters (pegasus.py / irw.py):
    same task count, object count and longest path."""
    fixed = make_graph({"mapreduce": "mapreduce"}.get(family, family),
                       seed=0)
    g = Recipe(family, n).build()
    g.validate()
    assert g.task_count == fixed.task_count == n
    assert g.object_count == fixed.object_count
    assert g.longest_path() == fixed.longest_path()


@pytest.mark.parametrize("family", sorted(RECIPE_FAMILIES))
@pytest.mark.parametrize("n", [40, 150, 400])
def test_recipes_scale_to_any_task_count(family, n):
    g = Recipe(family, n, seed=1).build()
    g.validate()
    assert abs(g.task_count - n) / n < 0.12
    assert all(t.cpus <= 4 for t in g.tasks)
    assert all(t.expected_duration is not None for t in g.tasks)
    assert all(o.expected_size is not None for o in g.objects)


def test_recipe_determinism_and_seed_independence():
    a = Recipe("montage", 77, seed=2).build()
    b = Recipe("montage", 77, seed=2).build()
    c = Recipe("montage", 77, seed=3).build()
    assert [t.duration for t in a.tasks] == [t.duration for t in b.tasks]
    assert [t.duration for t in a.tasks] != [t.duration for t in c.tasks]
    assert a.name == "montage-77-s2" and c.name == "montage-77-s3"


def test_recipe_dists_are_knobs():
    heavy = Recipe("mapreduce", 41, duration_dist=("const", 3.0),
                   size_dist=("const", 2.0), cpus_dist=("const", 1.0))
    light = Recipe("mapreduce", 41)
    gh, gl = heavy.build(), light.build()
    assert gh.total_duration == pytest.approx(3.0 * gl.task_count
                                              * np.mean([120, 80, 30]),
                                              rel=0.35)
    assert gh.total_duration > 2.0 * gl.total_duration
    assert max(t.cpus for t in gh.tasks) == 1
    with pytest.raises(KeyError, match="unknown distribution"):
        sample_dist(np.random, ("weibull", 1.0))


def test_instance_rng_seed_mixes_family_size_seed():
    """The seed-collision audit: any coordinate change moves the RNG
    stream, so manifests mixing families/sizes/seeds never alias."""
    seeds = {instance_rng_seed(f, n, s)
             for f in RECIPE_FAMILIES for n in (77, 104) for s in (0, 1)}
    assert len(seeds) == len(RECIPE_FAMILIES) * 2 * 2


def test_parse_instance_grammar():
    rec = parse_instance("cybershake-257-s4")
    assert (rec.name, rec.n_tasks, rec.seed) == ("cybershake", 257, 4)
    assert parse_instance("montage") is None
    assert parse_instance("nosuchfamily-10-s0") is None
    assert parse_instance("montage-77") is None
    with pytest.raises(KeyError, match="unknown recipe family"):
        Recipe("nosuch", 10)


# ------------------------------------------------- registry + seed audit


def test_recipe_instances_registered():
    assert set(RECIPE_INSTANCES) == set(DATASETS["recipes"])
    for name in SURVEY_GRAPHS["recipes"]:
        assert name in DATASETS["recipes"]
    g = make_graph("montage-77-s0")
    assert g.task_count == 77


def test_make_graph_seed_plumbing():
    """Per-instance seeds ride in names; two same-recipe different-seed
    manifest entries build distinct graphs through the one
    ``encode_graph_batch(seed=0)`` call (the ISSUE-5 regression)."""
    enc = encode_graph_batch(["montage-77-s0", "montage-77-s1"], seed=0)
    a, b = enc["montage-77-s0"][0], enc["montage-77-s1"][0]
    assert [t.duration for t in a.tasks] != [t.duration for t in b.tasks]
    # name-embedded and argument seeds compose (offset semantics)
    g = make_graph("montage-77-s0", seed=1)
    assert ([t.duration for t in g.tasks]
            == [t.duration for t in b.tasks])
    # classic generators gain the @s suffix for the same purpose
    x = make_graph("crossv@s2")
    y = make_graph("crossv", seed=2)
    assert [t.duration for t in x.tasks] == [t.duration for t in y.tasks]
    enc2 = encode_graph_batch(["crossv@s0", "crossv@s2"], seed=0)
    assert ([t.duration for t in enc2["crossv@s0"][0].tasks]
            != [t.duration for t in enc2["crossv@s2"][0].tasks])


def test_make_graph_unknown_name_message():
    with pytest.raises(KeyError, match="recipe instance"):
        make_graph("definitely-not-a-graph")


# ------------------------------------------------------------ manifests


def test_wfcommons_mini_manifest_contract():
    """The CI smoke dataset: >= 3 recipe families x 2 scales each
    (ISSUE-5 acceptance floor)."""
    fams = {}
    for name in WFCOMMONS_MINI.instances:
        rec = parse_instance(name)
        assert rec is not None, name
        fams.setdefault(rec.name, set()).add(rec.n_tasks)
    assert len(fams) >= 3
    assert all(len(scales) >= 2 for scales in fams.values())
    graphs = build_dataset(WFCOMMONS_MINI)
    assert set(graphs) == set(WFCOMMONS_MINI.instances)
    for g in graphs.values():
        g.validate()


def test_get_manifest():
    assert get_manifest("wfcommons-mini") is WFCOMMONS_MINI
    assert get_manifest(WFCOMMONS_MINI) is WFCOMMONS_MINI
    d = get_manifest("default", per_family=1)
    assert d.instances == tuple(default_manifest(1).instances)
    assert "montage-77-s0" in d.instances
    with pytest.raises(KeyError, match="unknown dataset"):
        get_manifest("nope")
    assert "wfcommons-mini" in MANIFESTS


def test_estee_mapreduce_manifest_is_the_32_by_32_shuffle():
    """The survey's irw MapReduce cut to 32 maps x 32 reduces: every
    reduce reads one shard of every map, so O = E = 32 * 32 + 32."""
    man = get_manifest("estee-mapreduce")
    assert man.instances == ("mapreduce-65-s0",)
    g, = build_dataset(man).values()
    spec = encode_graph(g)
    assert (spec.T, spec.O, spec.E) == (65, 1056, 1056)
    assert compute_bucket_edges(man) == (96,)


# ----------------------------------------------- adaptive bucket edges


def test_compute_bucket_edges_quantiles():
    # pure counts: upper quantiles rounded up to the pad multiple
    assert compute_bucket_edges([10, 20, 100, 300], k=2) == (32, 320)
    assert compute_bucket_edges([10, 20, 100, 300], k=1) == (320,)
    # collapsing quantiles dedupe to fewer edges
    assert compute_bucket_edges([50, 50, 50], k=3) == (64,)
    with pytest.raises(ValueError, match="k >= 1"):
        compute_bucket_edges([10], k=0)


def test_compute_bucket_edges_cover_dataset():
    edges = compute_bucket_edges(WFCOMMONS_MINI)
    assert edges == (128, 288)              # retune here if sizes move
    counts = [g.task_count for g in build_dataset(WFCOMMONS_MINI).values()]
    assert max(counts) <= edges[-1]
    assert all(e % 32 == 0 for e in edges)
    # derived edges drive the bucketing layer without overflow
    _, groups = encode_graph_batch(WFCOMMONS_MINI.instances, bucket=True,
                                   t_edges=edges, overflow="error")
    assert [grp.shape[0] for grp in groups] == list(edges)
    assert sum(len(grp.names) for grp in groups) == 6


def test_compute_w_buckets():
    assert compute_w_buckets(["8x4", "1x8+4x2"]) == (8,)
    assert compute_w_buckets(["8x4", "16x4", "3x2"]) == (4, 8, 16)


# ------------------------------------------------------ overflow policy


def test_t_bucket_overflow_policies():
    assert t_bucket(100, (32, 64)) == 128            # derive (default)
    assert t_bucket(129, (32, 64), overflow="derive") == 192
    with pytest.raises(ValueError, match="exceeds the largest bucket edge"):
        t_bucket(100, (32, 64), overflow="error")
    with pytest.raises(ValueError, match="unknown overflow policy"):
        t_bucket(100, (32, 64), overflow="wat")
    # a typo'd policy fails even when T fits the edges — the mistake
    # must not lie dormant until the first oversized graph
    with pytest.raises(ValueError, match="unknown overflow policy"):
        t_bucket(10, (32, 64), overflow="eror")


def test_pad_specs_overflow_policies():
    spec = encode_graph(make_graph("montage-77-s0"))
    with pytest.raises(ValueError, match="exceeds the largest bucket edge"):
        pad_specs({"m": spec}, t_edges=(32, 64), overflow="error")
    groups = pad_specs({"m": spec}, t_edges=(32, 64))   # derived bucket
    assert groups[0].shape[0] == 128
    ok = pad_specs({"m": spec}, t_edges=(32, 96), overflow="error")
    assert ok[0].shape[0] == 96


# ------------------------------------------- parity over recipe graphs


@pytest.mark.parametrize("gname", SURVEY_GRAPHS["recipes"][:2])
def test_recipe_graphs_ref_vs_vectorized(gname):
    """Parity sweep over the registered recipe representatives — the
    satellite asking survey_names/dataset_of growth to reach the parity
    suites automatically."""
    import jax
    import random
    from repro.core import MiB, Simulator, Worker
    from repro.core.schedulers.fixed import FixedScheduler
    from repro.core.vectorized import make_simulator

    g = make_graph(gname, seed=0)
    W, cores, bw = 4, 4, 100 * MiB
    rng = random.Random(11)
    assign = {t: rng.randrange(W) for t in g.tasks}
    prios = {t: float(g.task_count - i) for i, t in enumerate(g.tasks)}
    rep = Simulator(g, [Worker(i, cores) for i in range(W)],
                    FixedScheduler(dict(assign), prios), netmodel="maxmin",
                    bandwidth=bw, msd=0.0).run()
    run = jax.jit(make_simulator(encode_graph(g), W, cores, "maxmin"))
    a = np.array([assign[t] for t in g.tasks], np.int32)
    p = np.array([prios[t] for t in g.tasks], np.float32)
    ms, xfer, ok = run(a, p, bandwidth=bw)[:3]
    assert bool(ok)
    assert float(ms) == pytest.approx(rep.makespan, rel=2e-3)
    assert float(xfer) == pytest.approx(rep.transferred_bytes, rel=1e-3)
