"""RG-LRU linear recurrence — Pallas TPU kernel (Griffin, arXiv:2402.19427).

Diagonal gated recurrence h_t = a_t * h_{t-1} + b_t over width-W channels.
Grid walks (batch, width tiles, chunks) with the chunk axis sequential; the
carried state (one tile-wide vector, padded to an (8, tile) VMEM tile) stays
resident while a ``fori_loop`` steps through the chunk rows — a VPU-bound
kernel whose HBM traffic is exactly one read of (a, b) and one write of h
per token, the memory-bound optimum for decode-style recurrences. Channels
are independent, so tiling the width keeps the three double-buffered
(chunk, tile) blocks inside scoped VMEM at any W (W=4096 untiled needs
24 MB against v5e's 16 MB limit).

Validated on CPU via ``interpret=True`` against ``jax.lax.associative_scan``
(tests/test_kernels.py); compiled for a described TPU v5e at
recurrentgemma-9b width in tests/test_tpu_compile.py; run on the chip
against the oracle by chip_smoke.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUBLANES = 8  # float32 sublane tile height
_LANES = 128
_MAX_TILE_W = 512  # 3 blocks x 2 buffers x (256 x 512) f32 = 3 MB of VMEM


def _tile_width(w: int) -> int:
    """Largest lane-aligned divisor of ``w`` up to ``_MAX_TILE_W``; the
    whole width when it has none (small widths: block == full dim)."""
    for t in range(_MAX_TILE_W, 0, -_LANES):
        if w % t == 0:
            return t
    return w


def _rglru_kernel(a_ref, b_ref, y_ref, h_scr, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros(h_scr.shape, h_scr.dtype)

    def step(t, h):
        a_t = a_ref[0, t, :].astype(jnp.float32)
        b_t = b_ref[0, t, :].astype(jnp.float32)
        h = a_t * h + b_t
        y_ref[0, t, :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[0, :])
    h_scr[...] = jnp.broadcast_to(h, h_scr.shape)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rglru_scan(a: jax.Array, b: jax.Array, *, chunk: int = 256,
               interpret: bool = False) -> jax.Array:
    """h_t = a_t * h_{t-1} + b_t, h_0 = 0.

    a, b: (B, L, W) -> h: (B, L, W) (fp32 recurrence, output in b.dtype).
    """
    B, L, W = a.shape
    assert L % chunk == 0, f"L={L} % chunk={chunk}"
    tw = _tile_width(W)
    grid = (B, W // tw, L // chunk)
    block = pl.BlockSpec((1, chunk, tw), lambda b_, w, c: (b_, c, w))
    return pl.pallas_call(
        functools.partial(_rglru_kernel, chunk=chunk),
        grid=grid,
        in_specs=[block, block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((B, L, W), b.dtype),
        scratch_shapes=[pltpu.VMEM((_SUBLANES, tw), jnp.float32)],
        interpret=interpret,
    )(a, b)
