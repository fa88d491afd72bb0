"""Compile the main path's TPU programs for a described v5e, no chip
attached: the Pallas waterfill kernel at real widths, one max-min survey
grid program and the sharded engine's program on a 4-chip mesh.  Each
must compile with Mosaic and carry the kernel (``tpu_custom_call``) —
the failures interpret mode cannot show (1-D vector layouts, ``bool``
loop carries) surface here at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import MiB, parse_cluster
from repro.core.graphs import encode_graph_batch, survey_names
from repro.core.vectorized import (DOWNLOAD_SLOTS, BucketedGridRunner,
                                   ShardedGridRunner)
from repro.kernels import ops
from repro.kernels.waterfill import waterfill_batch

POINTS = [dict(imode="exact", bandwidth=32 * MiB, msd=0.0,
               decision_delay=0.0),
          dict(imode="user", bandwidth=512 * MiB, msd=0.1,
               decision_delay=0.05)]


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with JAX's persistent cache off: an entry
    compiled for a chip that is not attached cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices[:4]), ("grid",))


@pytest.fixture
def native_pallas(monkeypatch):
    """The simulator on the TPU routing: the compiled (not interpreted)
    kernel, chosen as ``waterfill_impl="auto"`` chooses it on a TPU."""
    from repro.core.vectorized import sim

    monkeypatch.setattr(ops, "_waterfill_pallas", waterfill_batch)
    monkeypatch.setattr(sim, "_resolve_waterfill_impl", lambda impl: "pallas")


@pytest.fixture(scope="module")
def t160_w8():
    """The T160 shape bucket of the survey graphs on an 8x4 cluster."""
    encoded, groups = encode_graph_batch(survey_names(1), seed=0,
                                         bucket=True)
    grp = groups[0]
    return [encoded[n] for n in grp.names], grp, parse_cluster("8x4")


def _sds(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                sharding=sharding)


def _kernel_compiles(one_chip, Bt, W):
    F = W * DOWNLOAD_SLOTS
    i32 = jax.ShapeDtypeStruct((Bt, F), jnp.int32, sharding=one_chip)
    act = jax.ShapeDtypeStruct((Bt, F), jnp.bool_, sharding=one_chip)
    caps = jax.ShapeDtypeStruct((Bt, W), jnp.float32, sharding=one_chip)
    return waterfill_batch.lower(i32, i32, act, caps, caps).compile()


@pytest.mark.parametrize("W", [8, 32])
def test_waterfill_kernel_compiles(one_chip, W):
    assert "tpu_custom_call" in _kernel_compiles(one_chip, 1, W).as_text()


def test_waterfill_kernel_batched_compiles(one_chip):
    compiled = _kernel_compiles(one_chip, 96, 32)
    assert "tpu_custom_call" in compiled.as_text()


def test_maxmin_grid_program_compiles(one_chip, native_pallas, t160_w8):
    entries, grp, cores = t160_w8
    runner = BucketedGridRunner(entries, "blevel", len(cores), cores,
                                shape=grp.shape, batch=grp.batch)
    args = (runner.bspec, *runner.grid_arrays(POINTS), runner.clusters)
    compiled = runner._fn.lower(*jax.tree_util.tree_map(
        lambda x: _sds(x, one_chip), args)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_program_compiles(mesh4, native_pallas, t160_w8):
    entries, grp, cores = t160_w8
    runner = ShardedGridRunner(entries, "blevel", len(cores), cores,
                               shape=grp.shape, batch=grp.batch, mesh=mesh4)
    D, S, M, DD, BW, SD = runner.grid_arrays(POINTS)
    B, N = D.shape[:2]
    _, rows = runner._row_chunks(B * N)
    row = NamedSharding(mesh4, P("grid"))

    def sharded_rows(x):                 # [rows, ...] split over the mesh
        x = np.asarray(x)
        return jax.ShapeDtypeStruct((rows,) + x.shape[1:], x.dtype,
                                    sharding=row)

    args = (jax.tree_util.tree_map(sharded_rows, runner.bspec),
            sharded_rows(D.reshape((B * N,) + D.shape[2:])),
            sharded_rows(S.reshape((B * N,) + S.shape[2:])),
            *(sharded_rows(np.tile(v, B)) for v in (M, DD, BW, SD)),
            _sds(runner.clusters, NamedSharding(mesh4, P())))
    compiled = runner._fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
