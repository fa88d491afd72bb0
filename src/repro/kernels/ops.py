"""Jit'd public wrappers around the Pallas kernels with XLA fallbacks.

``use_pallas=False`` (default) routes to the pure-jnp oracle in
``ref.py``; ``use_pallas=True`` compiles the Pallas kernel with Mosaic,
which needs a TPU — on any other backend the call fails instead of
quietly running something else.  Interpret mode (bit-accurate
kernel-body semantics on any backend) is the kernels' own
``interpret=True`` argument, for callers that ask for it: the CPU test
suite steers these wrappers onto it (``tests/conftest.py``).
"""
from __future__ import annotations

import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention as _flash_pallas
from .ssd import ssd_scan as _ssd_pallas
from .waterfill import waterfill_batch as _waterfill_pallas


def _require_f32(op: str, **arrays) -> None:
    """The simulators are float32-only (the JX103 invariant checked
    statically by ``repro.analysis``): a float64 leaking in under x64
    mode would silently upcast the whole max-min pipeline and desync the
    Pallas kernels (f32 VMEM refs) from the jnp oracle.  Fail loudly at
    the wrapper boundary instead."""
    for name, x in arrays.items():
        if jnp.result_type(x) == jnp.float64:
            raise TypeError(
                f"kernels.{op}: argument {name!r} is float64; the "
                f"simulator pipeline is float32-only (cast with "
                f"jnp.float32 / .astype(jnp.float32) at the call site)")


def attention(q, k, v, *, causal=True, window=0, scale=None, kv_len=None,
              use_pallas=False, blk_q=128, blk_k=128):
    """Flash attention (GQA + sliding window).  See ref.attention_ref.

    ``window`` may be a traced scalar (per-layer window patterns inside
    ``lax.scan``) and ``kv_len`` a traced valid-prefix length; the Pallas
    kernel needs both static, so those cases route to the oracle.
    """
    if use_pallas and isinstance(window, int) and kv_len is None:
        return _flash_pallas(q, k, v, causal=causal, window=window,
                             scale=scale, blk_q=blk_q, blk_k=blk_k)
    return ref.attention_ref(q, k, v, causal=causal, window=window,
                             scale=scale, kv_len=kv_len)


def ssd(x, dt, A, B, C, D, *, use_pallas=False, blk_l=64):
    """Mamba-2 SSD chunked scan.  Oracle: ref.ssd_ref (naive recurrence);
    the XLA path uses the chunk-parallel dual form (same math, matmuls)."""
    if use_pallas:
        return _ssd_pallas(x, dt, A, B, C, D, blk_l=blk_l)
    return ref.ssd_chunked(x, dt, A, B, C, D, chunk=blk_l)


def waterfill(src, dst, active, caps_up, caps_down, *, use_pallas=False,
              rounds=None):
    """Batched max-min fairness rates.  See ref.waterfill_ref.

    Accepts ``[Bt, F]`` batches or a single ``[F]`` flow set — the
    unbatched form is what the vectorized simulator calls from inside
    its event loop (``core.vectorized.sim``): under an outer ``jax.vmap``
    the Pallas kernel's batch grid dimension *is* the vmap axis, so a
    whole batch of simulations becomes one kernel launch per event.
    """
    _require_f32("waterfill", caps_up=caps_up, caps_down=caps_down)
    unbatched = src.ndim == 1
    if unbatched:
        src, dst, active, caps_up, caps_down = (
            x[None] for x in (src, dst, active, caps_up, caps_down))
    if use_pallas:
        out = _waterfill_pallas(src, dst, active, caps_up, caps_down,
                                rounds=rounds)
    else:
        out = ref.waterfill_ref(src, dst, active, caps_up, caps_down)
    return out[0] if unbatched else out
