"""Blockwise online-softmax (flash) attention — Pallas TPU kernel.

Target: TPU MXU. Tiling: (block_q x head_dim) query tiles resident in VMEM,
streaming (block_k x head_dim) key/value tiles; running max / denominator /
accumulator live in VMEM scratch across the sequential kv grid axis.
GQA is handled in the k/v index maps (q head h reads kv head
``h * Hkv // Hq``).

Blocks come from the shapes (``_block``): the whole sequence where it is
at most ``_MAX_BLOCK`` positions, else ``_MAX_BLOCK``, halved while the
tiles would overrun ``_VMEM_BUDGET``. A (q block, k block) pair that the
causal mask or the window leaves wholly masked does no work, and the k/v
index maps clamp to the live range, so its step issues no new DMA. Only
pairs that straddle the diagonal, the window's edge or a ragged end build
a mask. q.k runs in the inputs' own dtype on the MXU with f32
accumulation; the softmax, ``p`` and p.v stay in f32.

Supports causal masking and sliding-window masking (``window > 0``); the
non-causal path serves the Whisper encoder.

Validated on CPU via ``interpret=True`` against ``ref.attention_ref``
(tests/test_kernels.py); compiled for a described TPU v5e at qwen2.5-3b and
DeepSeek-V2 prefill shapes in tests/test_tpu_compile.py; run on the chip
against the oracle by chip_smoke.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # TPU lane width; scratch minor dims padded to this
_MAX_BLOCK = 512
#: what the tiles may take of the v5e's 16 MiB default scoped VMEM
_VMEM_BUDGET = 12 * 2**20


def _vmem_bytes(bq: int, bk: int, d: int, dv: int, itemsize: int) -> int:
    """VMEM one grid step holds: the double-buffered q, k, v and output
    tiles, the m / l / acc scratch, and ~4 f32 (bq, bk) score temporaries."""
    tiles = 2 * (bq * d + bk * d + bk * dv + bq * dv) * itemsize
    return tiles + (2 * _LANES + dv) * bq * 4 + 4 * bq * bk * 4


def _block(n: int, d: int, dv: int, itemsize: int) -> int:
    """Block length for a sequence axis of ``n``: the whole axis or a
    multiple of 128, as large as ``_MAX_BLOCK`` and the VMEM budget allow."""
    blk = min(n, _MAX_BLOCK)
    while blk > _LANES and _vmem_bytes(blk, blk, d, dv, itemsize) > _VMEM_BUDGET:
        blk = _LANES * max(1, blk // 2 // _LANES)
    return blk


def _live_k_blocks(qi, *, causal, window, block_q, block_k, nk):
    """First and last k block that query block ``qi`` attends to."""
    first = 0
    if window > 0:
        first = jnp.maximum(qi * block_q - window + 1, 0) // block_k
    last = nk - 1
    if causal:
        last = jnp.minimum(((qi + 1) * block_q - 1) // block_k, nk - 1)
    return first, last


def _flash_kernel(
    q_ref, k_ref, v_ref,               # inputs
    o_ref,                             # output
    m_scr, l_scr, acc_scr,             # VMEM scratch
    *,
    scale: float,
    causal: bool,
    window: int,
    seq_len: int,
    block_q: int,
    block_k: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    q_lo = qi * block_q
    k_lo = ki * block_k
    first, last = _live_k_blocks(qi, causal=causal, window=window,
                                 block_q=block_q, block_k=block_k, nk=nk)
    live = (ki >= first) & (ki <= last)
    # pairs where some (q, k) is masked: past a ragged end, across the
    # diagonal or across the window's edge
    edge = k_lo + block_k > seq_len
    if causal:
        edge |= k_lo + block_k - 1 > q_lo
    if window > 0:
        edge |= k_lo < q_lo + block_q - window

    def update(masked: bool):
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                            # (bq, bk)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, dv)
        if masked:
            q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            mask = k_pos < seq_len
            if causal:
                mask &= k_pos <= q_pos
            if window > 0:
                mask &= k_pos > q_pos - window
            s = jnp.where(mask, s, NEG_INF)
            if seq_len % block_k:
                # the last kv block reads past the end, where the values are
                # undefined (NaN in interpret mode) and 0 * NaN would reach acc
                v_row = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
                v = jnp.where(v_row < seq_len, v, 0.0)

        m_prev = m_scr[:, :1]                                # (bq, 1)
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                               # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                      # (bq, 1)
        l_new = alpha * l_prev + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    pl.when(live & edge)(lambda: update(True))
    pl.when(live & ~edge)(lambda: update(False))

    @pl.when(ki == nk - 1)
    def _fin():
        denom = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D) -> (B, Hq, S, D).

    ``block_q`` / ``block_k`` default to ``_block`` of the shapes."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    dv = v.shape[-1]              # may differ from d (MLA: qk 192, v 128)
    assert sq == sk, "flash kernel is for self-attention (prefill/train)"
    assert hq % hkv == 0
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    itemsize = jnp.dtype(q.dtype).itemsize
    block_q = min(block_q or _block(sq, d, dv, itemsize), sq)
    block_k = min(block_k or _block(sk, d, dv, itemsize), sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    grid = (b, hq, nq, nk)
    live_range = functools.partial(_live_k_blocks, causal=causal, window=window,
                                   block_q=block_q, block_k=block_k, nk=nk)

    def kv_map(bi, h, qi, ki):
        # a step outside the live range maps to the nearest live block,
        # which is already resident: no new DMA
        first, last = live_range(qi)
        return (bi, h * hkv // hq, jnp.clip(ki, first, last), 0)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        seq_len=sk,
        block_q=block_q,
        block_k=block_k,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, h, qi, ki: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dv), lambda bi, h, qi, ki: (bi, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
