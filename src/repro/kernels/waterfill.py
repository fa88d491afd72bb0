"""Pallas TPU kernel: batched max-min fairness water-filling.

This is the simulator's inner loop (ESTEE paper §2 "Communication
model") reformulated for the MXU: per batched simulation, the flow ->
resource incidence is materialised as one transposed one-hot matrix
``[2W, F]`` so that per-resource flow counts and per-flow freezes become
dense matmuls; the progressive-filling rounds run in a ``fori_loop``
with everything resident in VMEM.  The batch dimension is the Pallas
grid — thousands of concurrent simulations (GA populations, bandwidth
sweeps) fill the TPU.

Mosaic lowers 2-D vectors only, so every value in the kernel is a row:
flows are ``[1, F]``, resources ``[1, 2W]`` (the upload and download
caps arrive already concatenated), and the minimal share is a ``[1, 1]``
keepdims reduction.  The ``frozen`` mask is carried as float 0/1 —
Mosaic cannot carry ``bool`` vectors through a loop.

The vectorized simulator routes here through ``kernels.ops.waterfill``
(``waterfill_impl="pallas"``, the TPU default): each simulator event
calls the kernel on its compact flow-slot pool (``[S]``, Bt=1) and the
outer ``jax.vmap`` over simulations lifts the grid to the whole batch
via the ``pallas_call`` batching rule.  The fixed ``rounds`` fori_loop
is a no-op once every flow froze, so results match the early-exiting
jnp progressive filling (``core.vectorized.waterfill``) bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NT = (((1,), (1,)), ((), ()))           # dot_general: [1, F] x [2W, F]^T


def _waterfill_kernel(src_ref, dst_ref, active_ref, cap_ref, rates_ref, *,
                      F, W, rounds):
    src = src_ref[...]                               # [1, F] i32
    dst = dst_ref[...]
    active = active_ref[...]                         # [1, F] f32 0/1
    cap0 = cap_ref[...]                              # [1, 2W] up ++ down

    # transposed one-hot incidence [2W, F]: resource r is used by flow f
    res = jax.lax.broadcasted_iota(jnp.int32, (2 * W, F), 0)
    inc_t = ((res == src) | (res == dst + W)).astype(jnp.float32)

    def per_resource(x):                             # [1, F] -> [1, 2W]
        return jax.lax.dot_general(x, inc_t, _NT,
                                   preferred_element_type=jnp.float32)

    def body(_, carry):
        rates, frozen, cap = carry
        live = active * (1.0 - frozen)                       # [1, F]
        counts = per_resource(live)
        share = jnp.where(counts > 0, cap / jnp.maximum(counts, 1.0),
                          jnp.inf)
        # idle resources carry inf shares; the finite-guard below zeroes
        # min_share once every flow froze (fixed-round fori tail)
        min_share = jnp.min(share, axis=1, keepdims=True)  # simlint: disable=PY205
        is_bn = ((share <= min_share * (1.0 + 1e-9)) &
                 (counts > 0)).astype(jnp.float32)            # [1, 2W]
        touches = jnp.dot(is_bn, inc_t,
                          preferred_element_type=jnp.float32)  # [1, F]
        freeze = jnp.where((live > 0) & (touches > 0), 1.0, 0.0)
        min_share = jnp.where(min_share < jnp.inf, min_share, 0.0)
        rates = jnp.where(freeze > 0, min_share, rates)
        cap = jnp.maximum(cap - min_share * per_resource(freeze), 0.0)
        return rates, jnp.maximum(frozen, freeze), cap

    carry = (jnp.zeros((1, F), jnp.float32), 1.0 - active, cap0)
    rates, _, _ = jax.lax.fori_loop(0, rounds, body, carry)
    rates_ref[...] = rates


@functools.partial(jax.jit, static_argnames=("rounds", "interpret"))
def waterfill_batch(src, dst, active, caps_up, caps_down, *, rounds=None,
                    interpret=False):
    """Max-min rates for a batch of flow sets.

    src, dst: i32[Bt, F]; active: bool/int8[Bt, F];
    caps_up, caps_down: f32[Bt, W].  Returns f32[Bt, F].
    ``interpret=True`` runs the kernel body through the Pallas
    interpreter (any backend); the default compiles it with Mosaic,
    which needs a TPU.
    """
    Bt, F = src.shape
    W = caps_up.shape[-1]
    if rounds is None:
        rounds = 2 * W
    kernel = functools.partial(_waterfill_kernel, F=F, W=W, rounds=rounds)
    # one [1, n] row per grid step: the batch axis is a squeezed block dim
    rows = lambda x: x.reshape(Bt, 1, x.shape[-1])  # noqa: E731
    spec = lambda n: pl.BlockSpec((None, 1, n), lambda b: (b, 0, 0))  # noqa: E731
    caps = jnp.concatenate([caps_up, caps_down], axis=-1)
    out = pl.pallas_call(
        kernel,
        grid=(Bt,),
        in_specs=[spec(F), spec(F), spec(F), spec(2 * W)],
        out_specs=spec(F),
        out_shape=jax.ShapeDtypeStruct((Bt, 1, F), jnp.float32),
        interpret=interpret,
    )(rows(src.astype(jnp.int32)), rows(dst.astype(jnp.int32)),
      rows(active.astype(jnp.float32)), rows(caps))
    return out.reshape(Bt, F)
