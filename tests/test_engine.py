"""Sharded survey engine (DESIGN.md §9): ``ShardedGridRunner`` must be
a pure execution-layout change — bit-identical to the vmap path — while
``DoubleBufferQueue`` streams chunks and the persistent compile cache
keeps warm workers compile-free.

The multi-device case runs in a subprocess under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` with *distinct*
graphs on different shards: identical rows on every device mask
cross-device contamination (a sum of equal values can look like a
select), so the parity grid deliberately mixes graph content across the
mesh, with a G < devices remainder so padded and idle shards are
exercised too.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import MiB
from repro.core.vectorized import (BucketedGridRunner, ShardedGridRunner,
                                   DoubleBufferQueue, make_grid_runner,
                                   trace_counter, cache_counter,
                                   cache_event_counts, exec_counter)
from repro.core.vectorized.engine import (CACHE_ENV, DEFAULT_CACHE_DIR,
                                          ExecutableStore,
                                          compile_cache_root,
                                          enable_compile_cache)
from repro.core.vectorized.scheduling import (spmd_safe_argsort,
                                              spmd_safe_sort)
from repro.launch.mesh import make_grid_mesh, make_test_mesh

import test_vectorized_dynamic as tvd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

POINTS = [dict(imode="exact", bandwidth=100 * MiB, msd=0.0,
               decision_delay=0.0, seed=3),
          dict(imode="user", bandwidth=32 * MiB, msd=0.1,
               decision_delay=0.05, seed=3),
          dict(imode="exact", bandwidth=32 * MiB, msd=0.0,
               decision_delay=0.0, seed=7)]


def full_result(runner, points):
    """The un-sliced ``SimResult[K, B, N]`` — every field, so parity
    checks cover ok/n_steps/n_events, not just the makespan."""
    return runner._execute(*runner.grid_arrays(points))


def assert_bitwise(res_a, res_b):
    for field, a, b in zip(res_a._fields, res_a, res_b, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=field)


# ------------------------------------------------------- DoubleBufferQueue

def test_queue_order_and_exactly_once():
    put_log = []
    q = DoubleBufferQueue(range(5), put=lambda x: (put_log.append(x), x)[1])
    assert list(q) == list(range(5))
    assert put_log == list(range(5))            # each batch put exactly once


def test_queue_prefetch_depth():
    """put(k+1) runs before batch k is consumed — depth-2, no deeper."""
    put_log = []
    q = DoubleBufferQueue(range(4), put=put_log.append)
    assert put_log == [0]                       # constructor primes batch 0
    next(q)
    assert put_log == [0, 1]                    # consuming 0 prefetched 1
    next(q)
    assert put_log == [0, 1, 2]


def test_queue_drains_last_batch():
    """The final batch comes out with no trailing put and a clean
    StopIteration — no sentinel leaks, no double-advance."""
    q = DoubleBufferQueue([7])
    assert next(q) == 7
    with pytest.raises(StopIteration):
        next(q)
    assert list(DoubleBufferQueue([])) == []
    assert list(DoubleBufferQueue(iter([1, 2]))) == [1, 2]


def test_queue_identity_put_default():
    assert list(DoubleBufferQueue((x * x for x in range(3)))) == [0, 1, 4]


# ------------------------------------------------------------ mesh helpers

def test_make_test_mesh_validates_device_count():
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        make_test_mesh(shape=(64, 64))


def test_make_grid_mesh():
    m = make_grid_mesh(1)
    assert m.axis_names == ("grid",) and m.devices.size == 1
    full = make_grid_mesh()
    assert full.devices.size == len(jax.devices())
    with pytest.raises(RuntimeError, match="1-D grid mesh"):
        make_grid_mesh(len(jax.devices()) + 1)
    with pytest.raises(RuntimeError):
        make_grid_mesh(0)


# ------------------------------------------- SPMD-safe sort replacements

@pytest.mark.parametrize("trial", range(8))
def test_spmd_safe_sort_matches_numpy(trial):
    rng = np.random.default_rng(trial)
    n = int(rng.integers(1, 17))
    row = rng.standard_normal(n).astype(np.float32)
    # adversarial values the rank trick must order exactly like sort:
    # signed zeros compare equal, infinities sit at the ends
    row[rng.integers(0, n)] = np.float32(-0.0)
    if n > 2:
        row[rng.integers(0, n)] = np.float32(np.inf)
        row[rng.integers(0, n)] = np.float32(-np.inf)
    got = np.asarray(spmd_safe_sort(jnp.asarray(row)))
    np.testing.assert_array_equal(got, np.sort(row))


@pytest.mark.parametrize("trial", range(8))
def test_spmd_safe_argsort_matches_stable_argsort(trial):
    rng = np.random.default_rng(100 + trial)
    n = int(rng.integers(1, 17))
    # heavy ties: stability (first-index-wins) is the contract the
    # schedulers' priority ordering depends on
    key = rng.integers(0, 4, n).astype(np.float32)
    key[rng.integers(0, n)] = np.float32(-0.0)
    got = np.asarray(spmd_safe_argsort(jnp.asarray(key)))
    want = np.asarray(jnp.argsort(jnp.asarray(key), stable=True))
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------- single-device parity

@pytest.fixture(scope="module")
def runner_pair():
    entries = [(tvd.mini_fork(), None), (tvd.mini_merge(), None)]
    vmap = BucketedGridRunner(entries, "blevel", 4, 2)
    with trace_counter() as tc:
        shard = ShardedGridRunner(entries, "blevel", 4, 2, devices=1)
        res_s = full_result(shard, POINTS)
    assert tc.count == 1        # one jit trace regardless of engine
    return vmap, shard, res_s


def test_sharded_matches_vmap_bitwise(runner_pair):
    vmap, _shard, res_s = runner_pair
    assert_bitwise(full_result(vmap, POINTS), res_s)
    assert np.asarray(res_s.ok).all()


def test_sharded_call_shape_matches_vmap(runner_pair):
    vmap, shard, _res = runner_pair
    ms_v, xf_v = vmap(POINTS)
    ms_s, xf_s = shard(POINTS)
    assert ms_s.shape == ms_v.shape == (2, len(POINTS))
    np.testing.assert_array_equal(ms_s, ms_v)
    np.testing.assert_array_equal(xf_s, xf_v)


def test_stream_chunking_is_inert(runner_pair):
    """stream_rows=2 splits G=6 rows into 3 chunks through the prefetch
    queue — same bits, still one trace (chunks share one shape)."""
    _vmap, _shard, res_s = runner_pair
    entries = [(tvd.mini_fork(), None), (tvd.mini_merge(), None)]
    with trace_counter() as tc:
        chunked = ShardedGridRunner(entries, "blevel", 4, 2, devices=1,
                                    stream_rows=2)
        res_c = full_result(chunked, POINTS)
    assert tc.count == 1
    assert_bitwise(res_c, res_s)


def test_row_chunks_round_to_device_multiples():
    entries = [(tvd.mini_fork(), None)]
    r = ShardedGridRunner(entries, "blevel", 4, 2, devices=1)
    assert r._row_chunks(6) == (6, 6)
    r.stream_rows = 4
    assert r._row_chunks(6) == (4, 8)           # 2 chunks, 2 pad rows
    r.n_devices = 4                             # chunk rounds up to 4|chunk
    assert r._row_chunks(6) == (4, 8)
    r.stream_rows = 1
    assert r._row_chunks(6) == (4, 8)


def test_make_grid_runner_dispatch():
    entries = [(tvd.mini_fork(), None)]
    assert type(make_grid_runner(entries, "blevel", 4, 2)) \
        is BucketedGridRunner
    r = make_grid_runner(entries, "blevel", 4, 2, engine="sharded",
                         devices=1, stream_rows=3)
    assert isinstance(r, ShardedGridRunner) and r.stream_rows == 3
    with pytest.raises(TypeError, match="unknown engine"):
        make_grid_runner(entries, "blevel", 4, 2, engine="pmap")


def test_sharded_rejects_gridless_mesh():
    with pytest.raises(ValueError, match="'grid' axis"):
        ShardedGridRunner([(tvd.mini_fork(), None)], "blevel", 4, 2,
                          mesh=make_test_mesh(shape=(1, 1)))


# ------------------------------------------------- persistent cache

@pytest.fixture
def scoped_cache_dir(tmp_path, monkeypatch):
    """A cache directory of the test's own: ``$JAX_COMPILATION_CACHE_DIR``
    would otherwise win over it."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.delenv(CACHE_ENV, raising=False)
    old = jax.config.jax_compilation_cache_dir
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", old)
    compilation_cache.reset_cache()     # re-latch to the restored config


@pytest.fixture
def no_cache_dir():
    """No persistent cache directory for the test, whatever the
    environment configured."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", old)
    compilation_cache.reset_cache()


def test_cache_counter_without_cache_dir(no_cache_dir):
    """Without a cache dir nothing can *hit*; fresh compiles still
    count as misses (jax's cache feature flag is on by default), which
    is what makes the miss odometer an honest fresh-compile counter."""
    assert jax.config.jax_compilation_cache_dir is None
    with cache_counter() as cc:
        BucketedGridRunner([(tvd.mini_fork(), None)], "greedy", 4, 2)(
            POINTS[:1])
    assert cc.hits == 0 and cc.misses >= 1


def test_cache_miss_then_populated(scoped_cache_dir):
    """Enabling the cache mid-process (after other tests compiled with
    no dir — the latched-singleton hazard ``enable_compile_cache``
    resets) makes the next compile a counted *miss* that persists its
    entry; the global odometer and the scoped delta agree."""
    from repro.core.vectorized import enable_compile_cache
    before = cache_event_counts()
    enable_compile_cache(scoped_cache_dir)
    with cache_counter() as cc:
        make_grid_runner([(tvd.mini_merge(), None)], "tlevel", 4, 2,
                         engine="sharded", devices=1)(POINTS[:1])
    assert cc.misses >= 1 and cc.hits == 0
    after = cache_event_counts()
    assert after["misses"] - before["misses"] == cc.misses
    assert any(scoped_cache_dir.iterdir())      # entry actually persisted


def test_cache_warm_worker_subprocess(tmp_path):
    """Cross-process warmth through ``cache_dir`` (both tiers): the
    cold worker traces + compiles and populates the XLA cache and the
    executable store; the warm worker serves the same request with
    *zero fresh traces and zero fresh compiles* — it deserializes the
    stored executable (the ISSUE-8 warm-start contract)."""
    code = textwrap.dedent("""
        import json, sys
        from repro.core import MiB
        from repro.core.graphs import make_graph
        from repro.core.vectorized import (make_grid_runner, trace_counter,
                                           cache_counter, exec_counter)
        with trace_counter() as tc, cache_counter() as cc, \\
                exec_counter() as xc:
            runner = make_grid_runner(
                [(make_graph("fork1", seed=0), None)], "blevel", 4, 2,
                engine="sharded", devices=1, cache_dir=sys.argv[1])
            ms, _ = runner([dict(imode="exact", bandwidth=100 * MiB,
                                 msd=0.0, decision_delay=0.0, seed=3)])
        print(json.dumps({"traces": tc.count, "hits": cc.hits,
                          "misses": cc.misses, "exec_hits": xc.hits,
                          "exec_misses": xc.misses, "ms": float(ms[0][0])}))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop(CACHE_ENV, None)             # the test's own cache_dir, cold

    def worker():
        out = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                             env=env, capture_output=True, text=True,
                             timeout=600)
        assert out.returncode == 0, out.stderr[-3000:]
        import json
        return json.loads(out.stdout.splitlines()[-1])

    cold = worker()
    warm = worker()
    assert cold["misses"] >= 1 and cold["hits"] == 0
    assert cold["traces"] == 1 and cold["exec_misses"] == 1
    assert warm["traces"] == 0                         # zero fresh traces
    assert warm["misses"] == 0                         # zero fresh compiles
    assert warm["exec_hits"] == 1
    assert warm["ms"] == cold["ms"]


def test_compile_cache_root_precedence(monkeypatch, tmp_path):
    """One place decides the directory: the variable, else the explicit
    dir, else the fixed path in the checkout — never a temp path."""
    monkeypatch.delenv(CACHE_ENV, raising=False)
    assert compile_cache_root() == DEFAULT_CACHE_DIR
    assert os.path.dirname(DEFAULT_CACHE_DIR) == ROOT
    assert compile_cache_root(tmp_path) == str(tmp_path)
    monkeypatch.setenv(CACHE_ENV, str(tmp_path / "env"))
    assert compile_cache_root() == str(tmp_path / "env")
    assert compile_cache_root(tmp_path) == str(tmp_path / "env")


def test_enable_compile_cache_keeps_env_dir(monkeypatch, tmp_path):
    """Under ``$JAX_COMPILATION_CACHE_DIR`` no code sets another
    directory: an explicit ``cache_dir`` loses to the variable."""
    monkeypatch.setenv(CACHE_ENV, str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache(tmp_path / "explicit") == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before


# ------------------------------------------------- executable store

def test_exec_store_save_error_is_counted(tmp_path):
    """A failed save never fails the run, but it is counted — the next
    worker will miss, and the survey prints the count."""
    store = ExecutableStore(tmp_path)
    with exec_counter() as xc:
        store.save(("k",), object())     # not a compiled executable
    assert xc.save_errors == 1 and not any(tmp_path.iterdir())


def test_exec_store_roundtrip_in_process(tmp_path):
    """Tier-2 warm start without leaving the process: a second runner
    with the same program + shapes loads the stored executable (zero
    traces) and returns bit-identical results."""
    entries = [(tvd.mini_fork(), None)]
    with trace_counter() as tc, exec_counter() as xc:
        r1 = ShardedGridRunner(entries, "blevel", 4, 2, devices=1,
                               exec_dir=tmp_path)
        a = full_result(r1, POINTS)
    assert tc.count == 1 and xc.misses == 1 and xc.hits == 0
    assert any(tmp_path.iterdir())              # entry actually persisted
    with trace_counter() as tc, exec_counter() as xc:
        r2 = ShardedGridRunner(entries, "blevel", 4, 2, devices=1,
                               exec_dir=tmp_path)
        b = full_result(r2, POINTS)
    assert tc.count == 0 and xc.hits == 1 and xc.misses == 0
    assert_bitwise(a, b)


def test_exec_store_keys_separate_programs(tmp_path):
    """A different program (here: netmodel) with identical argument
    shapes must miss, not load the wrong executable."""
    entries = [(tvd.mini_fork(), None)]
    ShardedGridRunner(entries, "blevel", 4, 2, devices=1,
                      exec_dir=tmp_path)(POINTS[:1])
    with exec_counter() as xc:
        ShardedGridRunner(entries, "blevel", 4, 2, netmodel="simple",
                          devices=1, exec_dir=tmp_path)(POINTS[:1])
    assert xc.misses == 1 and xc.hits == 0
    assert len(list(tmp_path.iterdir())) == 2   # both programs stored


def test_exec_store_corrupt_entry_falls_back(tmp_path):
    """A corrupt/stale store entry degrades to a miss — recompile and
    overwrite, same results — never a crash or a wrong program."""
    entries = [(tvd.mini_fork(), None)]
    r1 = ShardedGridRunner(entries, "blevel", 4, 2, devices=1,
                           exec_dir=tmp_path)
    a = full_result(r1, POINTS)
    for f in tmp_path.iterdir():
        f.write_bytes(b"not a pickled executable")
    with trace_counter() as tc, exec_counter() as xc:
        r2 = ShardedGridRunner(entries, "blevel", 4, 2, devices=1,
                               exec_dir=tmp_path)
        b = full_result(r2, POINTS)
    assert tc.count == 1 and xc.misses == 1 and xc.hits == 0
    assert_bitwise(a, b)


# ------------------------------------------------- 8-device subprocess

def test_eight_device_parity_subprocess():
    """The acceptance grid: 2 schedulers x 2 netmodels, distinct graphs
    across shards, G=6 rows on 8 devices (uneven remainder + idle
    shards), bitwise equality on every SimResult field, one jit trace
    per (scheduler, netmodel) group."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        import jax
        from repro.core import MiB
        from repro.core.graphs import make_graph
        from repro.core.vectorized import (BucketedGridRunner,
                                           ShardedGridRunner, trace_counter)
        assert len(jax.devices()) == 8

        POINTS = [dict(imode="exact", bandwidth=100 * MiB, msd=0.0,
                       decision_delay=0.0, seed=3),
                  dict(imode="user", bandwidth=32 * MiB, msd=0.1,
                       decision_delay=0.05, seed=3),
                  dict(imode="exact", bandwidth=32 * MiB, msd=0.0,
                       decision_delay=0.0, seed=7)]

        def full(runner, points):
            return runner._execute(*runner.grid_arrays(points))

        entries = [(make_graph("fork1", seed=0), None),
                   (make_graph("merge_neighbours", seed=0), None)]
        for sched in ("blevel", "etf"):
            for netmodel in ("maxmin", "simple"):
                v = BucketedGridRunner(entries, sched, 4, 2,
                                       netmodel=netmodel)
                rv = full(v, POINTS)
                with trace_counter() as tc:
                    s = ShardedGridRunner(entries, sched, 4, 2,
                                          netmodel=netmodel)
                    rs = full(s, POINTS)
                assert s.n_devices == 8, s.n_devices
                assert tc.count == 1, (sched, netmodel, tc.count)
                for f, a, b in zip(rv._fields, rv, rs, strict=True):
                    np.testing.assert_array_equal(
                        np.asarray(a), np.asarray(b),
                        err_msg=f"{sched}/{netmodel}/{f}")
                assert np.asarray(rs.ok).all(), (sched, netmodel)
        print("ENGINE-8DEV-OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "ENGINE-8DEV-OK" in out.stdout, out.stderr[-3000:]
