"""The benchmark's own tests run on the CPU (``python -m pytest bench/``):
four host devices stand in for a four-chip mesh, and the Pallas
waterfill runs in interpret mode where a cell asks for the max-min
network model, as it would compile on the chip."""
import functools
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def interpret_waterfill(monkeypatch):
    """The program picks the jnp waterfill off the chip; steer it onto the
    Pallas kernel in interpret mode, the kernel the chip compiles."""
    from repro.core.vectorized import sim
    from repro.kernels import ops

    monkeypatch.setattr(sim, "_resolve_waterfill_impl", lambda impl: "pallas")
    monkeypatch.setattr(ops, "_waterfill_pallas", functools.partial(
        ops._waterfill_pallas, interpret=True))
