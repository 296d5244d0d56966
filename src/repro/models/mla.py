"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

KV is compressed to a ``kv_lora_rank`` latent (plus a shared RoPE key); the
decode path runs entirely in latent space with the up-projections absorbed
into the query — the KV cache stores only (c_kv, k_rope), which is what makes
the 32k/128-batch decode shapes feasible.

Rope on the 64 rope dims uses YaRN where ``rope_factor`` > 1 (DeepSeek-V2's
``rope_scaling``): frequencies blended between interpolated and
extrapolated by a linear ramp over the correction range of ``beta_fast``
and ``beta_slow`` rotations, and the softmax scale multiplied by mscale²,
at every position, in both paths. Rope pairs are the two halves of the
rope dims (HF's checkpoint interleaves them and permutes to halves before
rotating; with seeded weights the two layouts are one relabelling).
"""

from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops, ref
from repro.models.layers import apply_rope
from repro.utils.params import ParamBuilder
from repro.utils.sharding import shard


def init_mla(b: ParamBuilder, name: str, cfg: ModelConfig):
    sub = b.sub(name)
    D, H = cfg.d_model, cfg.num_heads
    nope, rdim, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    if qr:
        sub.param("w_dq", (D, qr), (None, None))
        sub.param("q_norm", (qr,), (None,), init="ones", dtype=jnp.float32)
        sub.param("w_uq", (qr, H * (nope + rdim)), (None, "heads"))
    else:
        sub.param("w_q", (D, H * (nope + rdim)), (None, "heads"))
    sub.param("w_dkv", (D, kr + rdim), (None, None))
    sub.param("kv_norm", (kr,), (None,), init="ones", dtype=jnp.float32)
    sub.param("w_uk", (kr, H * nope), (None, "heads"))
    sub.param("w_uv", (kr, H * vd), (None, "heads"))
    sub.param("w_o", (H * vd, D), ("heads", None))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_freqs(cfg: ModelConfig) -> np.ndarray:
    """Inverse frequencies (rdim/2,) of the rope dims: plain, or YaRN's blend
    of ``theta``'s (extrapolated, the fast dims) and the same over
    ``rope_factor`` (interpolated, the slow dims)."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1:
        return extra

    def dim_of(rotations):
        return dim * math.log(cfg.rope_original_max_positions
                              / (rotations * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(dim_of(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(dim_of(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extra / cfg.rope_factor * ramp + extra * (1.0 - ramp)


def softmax_scale(cfg: ModelConfig) -> float:
    """1 / sqrt(qk width), times YaRN's mscale² where ``rope_mscale_all_dim``."""
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    if cfg.rope_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    return scale


def _rope(x, positions, cfg: ModelConfig):
    """Rope over x's last axis (the rope dims), YaRN's where configured.
    YaRN's cos and sin carry mscale / mscale_all_dim, which is 1 where the
    two are equal, as in DeepSeek-V2."""
    if cfg.rope_factor <= 1:
        return apply_rope(x, positions, cfg.rope_theta)
    return apply_rope(x, positions, cfg.rope_theta, freqs=rope_freqs(cfg))


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps) * scale
    return out.astype(x.dtype)


def _queries(p, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    H = cfg.num_heads
    nope, rdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        cq = _rms(x @ p["w_dq"], p["q_norm"], cfg.norm_eps)
        q = cq @ p["w_uq"]
    else:
        q = x @ p["w_q"]
    q = q.reshape(B, S, H, nope + rdim)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = _rope(q_rope, positions, cfg)
    return q_nope, q_rope


def _latents(p, x, cfg: ModelConfig, positions):
    """Compressed KV latent + shared rope key. x: (B, S, D)."""
    B, S, _ = x.shape
    kr, rdim = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    kv = x @ p["w_dkv"]
    c_kv = _rms(kv[..., :kr], p["kv_norm"], cfg.norm_eps)
    k_rope = _rope(kv[..., kr:].reshape(B, S, 1, rdim), positions, cfg)
    return c_kv, k_rope.reshape(B, S, rdim)


def apply_mla(p, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    """Full-sequence MLA (train / prefill). Returns (out, (c_kv, k_rope))."""
    B, S, _ = x.shape
    H = cfg.num_heads
    nope, rdim, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _queries(p, x, cfg, positions)
    c_kv, k_rope = _latents(p, x, cfg, positions)

    k_nope = (c_kv @ p["w_uk"]).reshape(B, S, H, nope)
    v = (c_kv @ p["w_uv"]).reshape(B, S, H, vd)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, rdim))], axis=-1)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "heads", None)
    o = ops.attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=True, scale=softmax_scale(cfg),
    )
    o = o.transpose(0, 2, 1, 3).reshape(B, S, H * vd)
    o = shard(o, "batch", None, "heads")
    return o @ p["w_o"], (c_kv, k_rope)


def apply_mla_decode(p, x, cfg: ModelConfig, cache_ckv, cache_krope, pos):
    """One-token MLA decode with absorbed up-projections.

    x: (B, 1, D); cache_ckv: (B, S, kv_lora); cache_krope: (B, S, rdim).
    Returns (out, new_ckv, new_krope).
    """
    B = x.shape[0]
    H = cfg.num_heads
    nope, rdim, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kr = cfg.kv_lora_rank
    s_cache = cache_ckv.shape[1]

    posv = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope = _queries(p, x, cfg, posv)          # (B,1,H,nope), (B,1,H,rdim)
    c_kv, k_rope = _latents(p, x, cfg, posv)            # (B,1,kr), (B,1,rdim)

    # one-hot where-write: keeps the latent cache sequence-sharded (see
    # layers.apply_attention_decode)
    hit = (jnp.arange(s_cache) == pos)[None, :, None]
    new_ckv = jnp.where(hit, c_kv.astype(cache_ckv.dtype), cache_ckv)
    new_krope = jnp.where(hit, k_rope.astype(cache_krope.dtype), cache_krope)

    # absorb W_UK into the query: q_tilde (B,1,H,kr)
    w_uk = p["w_uk"].reshape(kr, H, nope)
    q_tilde = jnp.einsum("bshn,khn->bshk", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32))
    s = jnp.einsum("bshk,bSk->bhsS", q_tilde, new_ckv.astype(jnp.float32))
    s = s + jnp.einsum("bshr,bSr->bhsS", q_rope.astype(jnp.float32), new_krope.astype(jnp.float32))
    s = s * softmax_scale(cfg)
    valid = jnp.arange(s_cache)[None, :] <= pos
    s = jnp.where(valid[:, None, None, :] if valid.ndim == 2 else valid, s, ref.NEG_INF)
    pw = jax.nn.softmax(s, axis=-1)                      # (B,H,1,S)
    lat = jnp.einsum("bhsS,bSk->bshk", pw, new_ckv.astype(jnp.float32))  # (B,1,H,kr)
    w_uv = p["w_uv"].reshape(kr, H, vd)
    o = jnp.einsum("bshk,khv->bshv", lat, w_uv.astype(jnp.float32))
    o = o.reshape(B, 1, H * vd).astype(x.dtype)
    return o @ p["w_o"], new_ckv, new_krope
