"""Survey-runner plumbing (benchmarks/survey.py) without running sims:
estee CSV schema, grid expansion, graph batch-encoding helpers, and the
counter-based hash shared by the ``random``/``random-det`` twins."""
import numpy as np

from benchmarks import survey
from repro.core.graphs import (DATASETS, SURVEY_GRAPHS, encode_graph_batch,
                               survey_names)
from repro.core.schedulers.det import counter_choice


def test_schema_matches_estee_frame():
    """estee frame columns + the appended dataset column (trailing so
    older consumers reading by position stay compatible; trend.py
    tolerates it by construction)."""
    assert survey.SCHEMA == ("graph_name", "cluster_name", "bandwidth",
                             "netmodel", "scheduler_name", "imode",
                             "min_sched_interval", "time", "total_transfer",
                             "dataset")
    assert survey.AGREE_SCHEMA[-1] == "dataset"


def test_grid_points_expansion():
    pts = survey.grid_points(survey.MINI_GRID)
    g = survey.MINI_GRID
    assert len(pts) == (len(g["bandwidths_mib"]) * len(g["imodes"])
                        * len(g["msds"]))
    for p in pts:
        assert p["decision_delay"] == (0.05 if p["msd"] > 0 else 0.0)
    # acceptance floor: >= 3 graph families x >= 4 schedulers x 2 netmodels
    assert len(survey_names(g["graphs_per_family"])) >= 3
    assert len(g["schedulers"]) >= 4
    assert len(g["netmodels"]) == 2


def test_grids_name_parseable_clusters_incl_hetero():
    """Cluster axes are name strings of the shared grammar; both grids
    carry the heterogeneous ``1x8+4x2`` shape (paper §5 cluster column)."""
    from repro.core import parse_cluster

    for grid in (survey.MINI_GRID, survey.FULL_GRID):
        for cname in grid["clusters"]:
            cores = parse_cluster(cname)
            assert cores and all(c > 0 for c in cores)
        assert "1x8+4x2" in grid["clusters"]
    assert parse_cluster("1x8+4x2") == [8, 2, 2, 2, 2]


def test_check_compiles_contract():
    ok = dict(compiles=20, bucket_groups=20, buckets=["T160xO160xE416:a"])
    survey.check_compiles(ok)              # no raise
    import pytest

    with pytest.raises(AssertionError, match="recompiling per graph"):
        survey.check_compiles(dict(compiles=23, bucket_groups=20,
                                   buckets=["T160xO160xE416:a"]))


def test_report_prints_the_setup_split(capsys):
    stats = dict(compiles=1, bucket_groups=1, cluster_groups=["W4:4x2"],
                 dataset="default", t_edges="T_EDGES", cache_hits=0,
                 device=dict(platform="cpu", kind="cpu", count=1),
                 setup_s=dict(trace=1.25, compile=0.5, host=0.125))
    survey.report([], [], stats)
    out = capsys.readouterr().out.splitlines()
    assert "survey/cache_hits,0,0" in out
    assert {"survey/setup_trace_s,0,1.250", "survey/setup_compile_s,0,0.500",
            "survey/setup_host_s,0,0.125"} <= set(out)


def test_bucket_graph_batch_groups_survey_reps():
    """``encode_graph_batch(bucket=True)`` returns the padded groups the
    survey compiles once each; the mini representatives share one."""
    names = survey_names(1)
    encoded, groups = encode_graph_batch(names, seed=0, bucket=True)
    assert set(encoded) == set(names)
    assert sum(len(g.names) for g in groups) == len(names)
    assert len(groups) == 1
    grp = groups[0]
    assert grp.batch.durations.shape[0] == len(names)
    assert grp.label.startswith("T")


def test_estee_rows_schema():
    pts = survey.grid_points(survey.MINI_GRID)
    rows = survey.estee_rows("fork1", "8x4", "maxmin", "etf", pts,
                             np.arange(len(pts), dtype=np.float32),
                             np.zeros(len(pts), np.float32))
    assert len(rows) == len(pts)
    assert all(tuple(r) == survey.SCHEMA for r in rows)
    assert rows[0]["bandwidth"] == survey.MINI_GRID["bandwidths_mib"][0]


def test_survey_graphs_cover_every_family():
    assert set(SURVEY_GRAPHS) == set(DATASETS)
    for fam, names in SURVEY_GRAPHS.items():
        assert names, fam
        for n in names:
            assert n in DATASETS[fam], (fam, n)
    names = survey_names(2)
    assert len(names) == sum(min(2, len(v)) for v in SURVEY_GRAPHS.values())


def test_report_prints_the_backlog_peak_of_each_group(capsys):
    stats = dict(compiles=2, bucket_groups=2, cluster_groups=["W32:32x4"],
                 dataset="estee-mapreduce", t_edges=(96,), cache_hits=0,
                 device=dict(platform="cpu", kind="cpu", count=1),
                 setup_s=dict(trace=1.0, compile=0.5, host=0.25),
                 backlog_peaks={"T96/W32/greedy/maxmin": 478,
                                "T96/W32/greedy/simple": 0})
    survey.report([], [], stats)
    out = capsys.readouterr().out.splitlines()
    assert {"survey/backlog_peak/T96/W32/greedy/maxmin,0,478",
            "survey/backlog_peak/T96/W32/greedy/simple,0,0"} <= set(out)


def test_encode_graph_batch_builds_specs_once():
    batch = encode_graph_batch(["fastcrossv", "sipht"], seed=0)
    g, spec = batch["fastcrossv"]
    assert g.task_count == spec.T and g.object_count == spec.O


def test_dataset_axis_default_vs_manifest():
    """The --dataset axis: 'default' keeps the per-family reps under
    the tuned T_EDGES; manifests derive their own bucket edges."""
    from repro.core.vectorized.specs import T_EDGES
    from repro.workloads import WFCOMMONS_MINI, compute_bucket_edges

    ds, names, t_edges = survey.dataset_axis(survey.MINI_GRID)
    assert (ds, t_edges) == ("default", None)
    assert names == survey_names(survey.MINI_GRID["graphs_per_family"])

    grid = dict(survey.MINI_GRID, dataset="wfcommons-mini")
    ds, items, t_edges = survey.dataset_axis(grid)
    assert ds == "wfcommons-mini"
    # manifests come back prebuilt — (name, graph) pairs, built once
    assert tuple(n for n, _ in items) == WFCOMMONS_MINI.instances
    assert all(g.task_count > 0 for _, g in items)
    assert t_edges == compute_bucket_edges(WFCOMMONS_MINI)
    assert t_edges != T_EDGES and t_edges[-1] >= 204
    # prebuilt pairs flow through encode_graph_batch unchanged
    from repro.core.graphs import encode_graph_batch
    enc = encode_graph_batch(items[:2], seed=0)
    assert enc[items[0][0]][0] is items[0][1]


def test_estee_rows_carry_dataset():
    pts = survey.grid_points(survey.MINI_GRID)[:2]
    rows = survey.estee_rows("montage-77-s0", "8x4", "maxmin", "etf", pts,
                             np.zeros(2, np.float32), np.zeros(2, np.float32),
                             dataset="wfcommons-mini")
    assert all(r["dataset"] == "wfcommons-mini" for r in rows)


def test_counter_hash_matches_vectorized_twin():
    """The pure-Python counter hash (random-det) and the JAX one
    (vectorized random) must be bit-identical."""
    import jax
    import jax.numpy as jnp
    from repro.core.vectorized.scheduling import _mix32

    seeds = np.array([0, 1, 7, 12345], np.uint32)
    ctrs = np.arange(50, dtype=np.uint32)
    for s in seeds:
        jx = jax.jit(lambda c: _mix32(
            jnp.uint32(s) * jnp.uint32(0x9E3779B9) + c + jnp.uint32(1)))(ctrs)
        for c, h in zip(ctrs, np.asarray(jx)):
            for n in (1, 2, 3, 8):
                assert counter_choice(int(s), int(c), n) == int(h) % n


def test_device_info_names_the_backend():
    import jax

    dev = survey.device_info()
    assert dev == {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())}


def test_chip_smoke_grid_is_one_w32_group_of_24_points():
    """The smoke slice: FULL_GRID's points, the paper's widest clusters
    in one W=32 group, 2 schedulers x 2 netmodels."""
    import chip_smoke

    grid = chip_smoke.smoke_grid()
    assert len(survey.grid_points(grid)) == 24
    (w, names, cores), = survey.cluster_groups(grid["clusters"])
    assert w == 32 and names == ["32x4", "32x16"] and cores.shape == (2, 32)
    assert len(grid["schedulers"]) * len(grid["netmodels"]) == 4


def test_chip_smoke_refuses_without_tpu():
    """On the CPU the smoke test exits non-zero before any work and
    prints no result line."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=root,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr
