"""Blockwise online-softmax (flash) attention — Pallas TPU kernel.

Target: TPU MXU. Tiling: (block_q x head_dim) query tiles resident in VMEM,
streaming (block_k x head_dim) key/value tiles; running max / denominator /
accumulator live in VMEM scratch across the sequential kv grid axis.
Blocks are 128-aligned for the MXU. GQA is handled in the k/v index maps
(q head h reads kv head ``h * Hkv // Hq``).

Supports causal masking and sliding-window masking (``window > 0``); the
non-causal path serves the Whisper encoder.

Validated on CPU via ``interpret=True`` against ``ref.attention_ref``
(tests/test_kernels.py); compiled for a described TPU v5e at qwen2.5-3b
prefill shapes in tests/test_tpu_compile.py; run on the chip against the
oracle by chip_smoke.py.
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

    q = q_ref[0, 0].astype(jnp.float32)                      # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                      # (bk, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                                # (bq, bk)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos < seq_len
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, :1]                                    # (bq, 1)
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                                   # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                          # (bq, 1)
    l_new = alpha * l_prev + p.sum(axis=1, keepdims=True)

    v = v_ref[0, 0].astype(jnp.float32)                      # (bk, d)
    if seq_len % block_k:
        # the last kv block reads past the end, where the values are
        # undefined (NaN in interpret mode) and 0 * NaN would reach acc
        v_row = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
        v = jnp.where(v_row < seq_len, v, 0.0)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

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
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D) -> (B, Hq, S, D)."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    dv = v.shape[-1]              # may differ from d (MLA: qk 192, v 128)
    assert sq == sk, "flash kernel is for self-attention (prefill/train)"
    assert hq % hkv == 0
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    grid = (b, hq, nq, nk)

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
            pl.BlockSpec((1, 1, block_k, d), lambda bi, h, qi, ki, hkv=hkv, hq=hq: (bi, h * hkv // hq, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda bi, h, qi, ki, hkv=hkv, hq=hq: (bi, h * hkv // hq, ki, 0)),
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
