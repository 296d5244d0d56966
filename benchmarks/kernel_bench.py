"""Kernel micro-benchmarks of the XLA path, wall-clock on the host it runs
on. The Pallas kernels are checked in interpret mode (tests/test_kernels.py),
compiled for a described TPU v5e (tests/test_tpu_compile.py) and run on the
chip against their oracles by chip_smoke.py; none of that is timed here.

name, us_per_call, derived GFLOP/s.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops


def _bench(fn, *args, n=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def run():
    rows = []
    key = jax.random.PRNGKey(0)
    B, H, S, D = 1, 8, 2048, 64
    q = jax.random.normal(key, (B, H, S, D), jnp.float32)
    k = jax.random.normal(key, (B, H, S, D), jnp.float32)
    v = jax.random.normal(key, (B, H, S, D), jnp.float32)
    fn = jax.jit(lambda q, k, v: ops.attention(q, k, v, impl="xla"))
    us = _bench(fn, q, k, v)
    flops = 4.0 * B * H * S * S * D * 0.5
    rows.append(dict(config="attention-xla-2k", us_per_call=round(us, 1),
                     gflops=round(flops / us / 1e3, 2)))

    Bm, L, Hm, P, N = 1, 2048, 8, 64, 64
    x = jax.random.normal(key, (Bm, L, Hm, P), jnp.float32) * 0.3
    dt = jax.nn.softplus(jax.random.normal(key, (Bm, L, Hm))) * 0.1
    a = -jnp.exp(jax.random.normal(key, (Hm,)) * 0.3)
    bm = jax.random.normal(key, (Bm, L, 1, N)) * 0.3
    cm = jax.random.normal(key, (Bm, L, 1, N)) * 0.3
    fn = jax.jit(lambda *t: ops.ssd(*t, chunk=256, impl="xla")[0])
    us = _bench(fn, x, dt, a, bm, cm)
    rows.append(dict(config="ssd-xla-2k", us_per_call=round(us, 1),
                     gflops=round(6.0 * Bm * L * Hm * P * N / us / 1e3, 2)))
    return rows


if __name__ == "__main__":
    for row in run():
        print(row)
