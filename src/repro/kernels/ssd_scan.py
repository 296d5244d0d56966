"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

State-space duality (arXiv:2405.21060) splits the linear recurrence into an
intra-chunk quadratic (attention-like, MXU-friendly) term and an inter-chunk
rank-1 state pass.  The kernel walks chunks sequentially along the last grid
axis, carrying the (head_dim x state) SSM state in VMEM scratch — the TPU
analogue of the paper's SM-resident state; chunk = 256 keeps the
(chunk x chunk) gate matrix and operand tiles inside VMEM and the matmuls
MXU-aligned.

Layout: the wrapper puts heads (groups) before length, so every block's two
minor dims are (chunk, P), (chunk, N) or (chunk, 1) — a multiple of 8 rows
by the full minor width, as the TPU's (8, 128) tiling requires. The step
sizes arrive as a (chunk, 1) column; the within-chunk cumulative decay is
formed from masked sums in f32 (no cumsum primitive, no MXU rounding). The
per-head decay rate is a scalar in SMEM.

Validated on CPU via ``interpret=True`` against ``ref.ssd_sequential``
(tests/test_kernels.py); compiled for a described TPU v5e at mamba2-130m
widths in tests/test_tpu_compile.py; run on the chip against the oracle by
chip_smoke.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref,   # inputs
    y_ref, h_ref,                         # outputs (per-chunk y, final state)
    h_scr,                                # VMEM scratch: carried state (P, N)
    *,
    chunk: int,
):
    hi = pl.program_id(1)
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros(h_scr.shape, h_scr.dtype)

    x = x_ref[0, 0].astype(jnp.float32)              # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)            # (Q, 1)
    a = a_ref[hi]                                    # scalar (SMEM)
    bm = b_ref[0, 0].astype(jnp.float32)             # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)             # (Q, N)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = row >= col
    da = jnp.broadcast_to(dt * a, (chunk, chunk))    # [r, t] = dt_r * a
    # inclusive cumsum within the chunk, as a row then as a column
    lrow = jnp.sum(jnp.where(row <= col, da, 0.0), axis=0, keepdims=True)  # (1, Q)
    lcol = jnp.sum(jnp.where(row == col, jnp.broadcast_to(lrow, (chunk, chunk)),
                             0.0), axis=1, keepdims=True)              # (Q, 1)

    # intra-chunk quadratic term
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)     # (Q, Q)
    gate = jnp.where(tri, cb * jnp.exp(lcol - lrow), 0.0)
    y = jax.lax.dot_general(gate, x * dt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)      # (Q, P)
    # inter-chunk: contribution of carried state
    h = h_scr[...]                                                   # (P, N)
    y += jnp.exp(lcol) * jax.lax.dot_general(
        cm, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update: h <- exp(ltot) h + sum_t exp(ltot - l_t) dt_t x_t B_t^T
    ltot = jnp.sum(dt * a, axis=0, keepdims=True)                    # (1, 1)
    w = jnp.exp(ltot - lcol) * dt                                    # (Q, 1)
    h_scr[...] = h * jnp.exp(ltot) + jax.lax.dot_general(
        (x * w).T, bm, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                          # (P, N)

    @pl.when(ci == nc - 1)
    def _fin():
        h_ref[0, 0] = h_scr[...].astype(h_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b_mat: jax.Array,
    c_mat: jax.Array,
    *,
    chunk: int = 256,
    interpret: bool = False,
):
    """Chunked SSD.

    x: (B, L, H, P); dt: (B, L, H); a: (H,); b_mat/c_mat: (B, L, G, N).
    Returns (y (B, L, H, P), h_final (B, H, P, N)); fp32 state.
    """
    B, L, H, P = x.shape
    G, N = b_mat.shape[2], b_mat.shape[3]
    assert L % chunk == 0, f"L={L} % chunk={chunk}"
    nc = L // chunk
    xt = jnp.swapaxes(x, 1, 2)                                   # (B, H, L, P)
    dtt = jnp.swapaxes(dt, 1, 2)[..., None]                      # (B, H, L, 1)
    bt = jnp.swapaxes(b_mat, 1, 2)                               # (B, G, L, N)
    ct = jnp.swapaxes(c_mat, 1, 2)

    grid = (B, H, nc)
    y, h = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c, G=G, H=H: (b, h * G // H, c, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c, G=G, H=H: (b, h * G // H, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",     # the op's name in a profile (bench/metrics/ssd_roofline.py)
    )(xt, dtt, a.astype(jnp.float32), bt, ct)
    return jnp.swapaxes(y, 1, 2), h
