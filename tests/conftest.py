import functools
import os
import sys

import pytest

# src-layout import without install
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(autouse=True)
def pallas_interpret(monkeypatch):
    """The suite runs on the CPU, where Mosaic cannot compile: steer the
    ``use_pallas=True`` wrappers of ``repro.kernels.ops`` onto the Pallas
    interpreter.  The program itself never picks interpret mode; a test
    that needs the compiled kernel (tests/test_tpu_compile.py) puts the
    originals back."""
    from repro.kernels import ops

    for name in ("_flash_pallas", "_ssd_pallas", "_waterfill_pallas"):
        monkeypatch.setattr(ops, name, functools.partial(
            getattr(ops, name), interpret=True))
