"""Framework bench: Pallas kernels vs jnp oracles — correctness max-err
of the kernel bodies (interpret mode, asked for explicitly) and the
XLA-path wall time per call on the device JAX runs on."""
from __future__ import annotations

import time

import numpy as np

from .common import write_csv


def _time(fn, *args, reps=3):
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run(fast=True):
    import jax
    import jax.numpy as jnp
    from repro.kernels import attention, ssd, waterfill, ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ssd import ssd_scan
    from repro.kernels.waterfill import waterfill_batch

    rng = np.random.default_rng(0)
    rows = []
    shapes = [(1, 8, 2, 256, 64), (2, 4, 4, 128, 64)]
    if not fast:
        shapes += [(1, 16, 4, 512, 128), (4, 8, 8, 256, 128)]
    for (B, Hq, Hkv, S, D) in shapes:
        q = jnp.asarray(rng.standard_normal((B, Hq, S, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
        xla = jax.jit(lambda q, k, v: attention(q, k, v, causal=True))
        t = _time(xla, q, k, v)
        o_p = flash_attention(q, k, v, causal=True, blk_q=64, blk_k=64,
                              interpret=True)
        err = float(jnp.max(jnp.abs(o_p - ref.attention_ref(q, k, v))))
        flops = 4.0 * B * Hq * S * S * D / 2
        name = f"attn_B{B}H{Hq}S{S}D{D}"
        print(f"kernels/{name},{t * 1e6:.0f},{flops / t / 1e9:.1f}")
        rows.append({"kernel": name, "wall_us": t * 1e6,
                     "gflops": flops / t / 1e9, "pallas_err": err})

    for (Bt, L, H, P, N) in [(2, 256, 4, 64, 32)]:
        x = jnp.asarray(rng.standard_normal((Bt, L, H, P)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.001, 0.1, (Bt, L, H)), jnp.float32)
        A = jnp.asarray(-rng.uniform(0.5, 2, (H,)), jnp.float32)
        Bm = jnp.asarray(rng.standard_normal((Bt, L, N)), jnp.float32)
        Cm = jnp.asarray(rng.standard_normal((Bt, L, N)), jnp.float32)
        Dm = jnp.ones((H,), jnp.float32)
        xla = jax.jit(lambda *a: ssd(*a))
        t = _time(xla, x, dt, A, Bm, Cm, Dm)
        y_p = ssd_scan(x, dt, A, Bm, Cm, Dm, blk_l=64, interpret=True)
        err = float(jnp.max(jnp.abs(y_p - ref.ssd_ref(x, dt, A, Bm, Cm, Dm))))
        name = f"ssd_B{Bt}L{L}H{H}"
        print(f"kernels/{name},{t * 1e6:.0f},{err:.2e}")
        rows.append({"kernel": name, "wall_us": t * 1e6, "pallas_err": err})

    for (Bt, F, W) in [(8, 64, 8)]:
        src = jnp.asarray(rng.integers(0, W, (Bt, F)), jnp.int32)
        dst = jnp.asarray(rng.integers(0, W, (Bt, F)), jnp.int32)
        act = jnp.asarray(rng.random((Bt, F)) < 0.5)
        caps = jnp.full((Bt, W), 100.0, jnp.float32)
        xla = jax.jit(lambda *a: waterfill(*a))
        t = _time(xla, src, dst, act, caps, caps)
        r_p = waterfill_batch(src, dst, act, caps, caps, interpret=True)
        err = float(jnp.max(jnp.abs(
            r_p - ref.waterfill_ref(src, dst, act, caps, caps))))
        name = f"waterfill_B{Bt}F{F}W{W}"
        print(f"kernels/{name},{t * 1e6:.0f},{err:.2e}")
        rows.append({"kernel": name, "wall_us": t * 1e6, "pallas_err": err})
    write_csv("kernels", rows)
    return rows
