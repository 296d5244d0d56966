"""Primitive layers shared by every architecture family.

Pure-functional: each ``init_*`` builds params via a ParamBuilder (recording
logical sharding axes); each ``apply`` is a plain function. Activations carry
logical sharding constraints via ``utils.sharding.shard`` (no-ops off-mesh).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.utils.params import ParamBuilder
from repro.utils.sharding import shard


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(b: ParamBuilder, name: str, dim: int, kind: str):
    sub = b.sub(name)
    sub.param("scale", (dim,), (None,), init="ones", dtype=jnp.float32)
    if kind == "layernorm":
        sub.param("bias", (dim,), (None,), init="zeros", dtype=jnp.float32)


def apply_norm(p, x: jax.Array, kind: str, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float, pct: float = 1.0,
               freqs=None) -> jax.Array:
    """x: (..., S, H, D) or (..., S, D); positions: (..., S) or (S,).

    ``pct`` < 1 applies rotary to the leading ``pct * D`` dims only
    (ChatGLM's 2d/partial rotary). ``freqs`` (d_rot/2,) replaces the plain
    inverse frequencies of ``theta`` (YaRN's, in MLA).
    """
    if theta <= 0:
        return x
    d = x.shape[-1]
    d_rot = int(d * pct)
    d_rot -= d_rot % 2
    xr, xp = x[..., :d_rot], x[..., d_rot:]
    freqs = rope_freqs(d_rot, theta) if freqs is None else jnp.asarray(freqs, jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, d_rot/2)
    if x.ndim == 4:  # (..., S, H, D): insert the head axis for broadcasting
        ang = jnp.expand_dims(ang, -2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = xr[..., : d_rot // 2], xr[..., d_rot // 2:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rot.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------------------
# dense / MLP
# ---------------------------------------------------------------------------

def init_mlp(b: ParamBuilder, name: str, cfg: ModelConfig, d_ff: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    sub = b.sub(name)
    gated = cfg.act in ("silu", "geglu")
    if gated:
        # separate up/gate projections: splitting a packed (d, 2*ff) matmul
        # output along the ff-sharded axis forces a cross-device resharding
        # (collective-permute per layer) under GSPMD — two matmuls don't.
        sub.param("w_up", (cfg.d_model, d_ff), (None, "ff"))
        sub.param("w_gate", (cfg.d_model, d_ff), (None, "ff"))
    else:
        sub.param("w_in", (cfg.d_model, d_ff), (None, "ff"))
    sub.param("w_out", (d_ff, cfg.d_model), ("ff", None))


def apply_mlp(p, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.act in ("silu", "geglu"):
        act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
        h = (x @ p["w_up"]) * act(x @ p["w_gate"])
    else:
        h = jax.nn.gelu(x @ p["w_in"])
    h = shard(h, "batch", None, "ff")
    return h @ p["w_out"]


# ---------------------------------------------------------------------------
# GQA attention (dense / moe / hybrid-local / encoder / vlm-self)
# ---------------------------------------------------------------------------

def init_attention(b: ParamBuilder, name: str, cfg: ModelConfig,
                   num_heads: Optional[int] = None, num_kv: Optional[int] = None):
    nh = num_heads or cfg.num_heads
    nkv = num_kv or cfg.num_kv_heads
    hd = cfg.head_dim_
    sub = b.sub(name)
    sub.param("w_q", (cfg.d_model, nh * hd), (None, "heads"))
    sub.param("w_k", (cfg.d_model, nkv * hd), (None, "kv_heads"))
    sub.param("w_v", (cfg.d_model, nkv * hd), (None, "kv_heads"))
    sub.param("w_o", (nh * hd, cfg.d_model), ("heads", None))
    if cfg.qkv_bias:
        sub.param("b_q", (nh * hd,), ("heads",), init="zeros")
        sub.param("b_k", (nkv * hd,), ("kv_heads",), init="zeros")
        sub.param("b_v", (nkv * hd,), ("kv_heads",), init="zeros")


def softmax_scale(cfg: ModelConfig) -> Optional[float]:
    """The config's ``attention_multiplier``, else None: 1/sqrt(head_dim)."""
    return cfg.attention_multiplier or None


def _project_qkv(p, x, cfg: ModelConfig, nh: int, nkv: int):
    B, S, _ = x.shape
    hd = cfg.head_dim_
    q = x @ p["w_q"]
    k = x @ p["w_k"]
    v = x @ p["w_v"]
    if "b_q" in p:
        q = q + p["b_q"]
        k = k + p["b_k"]
        v = v + p["b_v"]
    q = q.reshape(B, S, nh, hd)
    k = k.reshape(B, S, nkv, hd)
    v = v.reshape(B, S, nkv, hd)
    return q, k, v


def apply_attention(
    p,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    num_heads: Optional[int] = None,
    num_kv: Optional[int] = None,
):
    """Full-sequence self attention (train / prefill). Returns (out, (k, v))."""
    nh = num_heads or cfg.num_heads
    nkv = num_kv or cfg.num_kv_heads
    q, k, v = _project_qkv(p, x, cfg, nh, nkv)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_pct)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_pct)
    q = shard(q, "batch", None, "heads", None)
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    o = ops.attention(qh, kh, vh, causal=causal, window=window, scale=softmax_scale(cfg))
    o = o.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[1], -1)
    o = shard(o, "batch", None, "heads")
    return o @ p["w_o"], (kh, vh)


def quantize_kv(kh: jax.Array):
    """Per-(batch, head, position) symmetric int8 quantization.

    kh: (B, H, 1, hd) -> (int8 values, f32 scale (B, H, 1))."""
    amax = jnp.max(jnp.abs(kh.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(kh.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def apply_attention_decode(
    p,
    x: jax.Array,
    cfg: ModelConfig,
    cache_k: jax.Array,
    cache_v: jax.Array,
    pos: jax.Array,
    *,
    window: int = 0,
    num_heads: Optional[int] = None,
    num_kv: Optional[int] = None,
    cache_scales: Optional[Tuple[jax.Array, jax.Array]] = None,
):
    """One-token decode against a KV cache.

    x: (B, 1, D). cache_k/v: (B, Hkv, S_cache, hd). ``pos`` scalar int32 —
    number of tokens already in the cache. With ``window`` > 0 the cache is a
    ring buffer of size S_cache == window.

    ``cache_scales``: (k_scale, v_scale) each (B, Hkv, S_cache) f32 when the
    cache is int8-quantized. Returns (out, new_k, new_v[, new_scales]).
    """
    nh = num_heads or cfg.num_heads
    nkv = num_kv or cfg.num_kv_heads
    B = x.shape[0]
    hd = cfg.head_dim_
    s_cache = cache_k.shape[2]
    q, k, v = _project_qkv(p, x, cfg, nh, nkv)
    posv = jnp.full((B, 1), pos, jnp.int32)
    q = apply_rope(q, posv, cfg.rope_theta, cfg.rotary_pct)
    k = apply_rope(k, posv, cfg.rope_theta, cfg.rotary_pct)
    qh = q.transpose(0, 2, 1, 3)                        # (B, H, 1, hd)
    kh = k.transpose(0, 2, 1, 3)                        # (B, Hkv, 1, hd)
    vh = v.transpose(0, 2, 1, 3)
    slot = jnp.where(window > 0, pos % s_cache, jnp.minimum(pos, s_cache - 1))
    # one-hot where-write instead of dynamic-update-slice: elementwise ops
    # preserve a sequence-sharded cache layout under GSPMD (a DUS at a
    # dynamic index on a sharded dim forces gather/rematerialization)
    idx = jnp.arange(s_cache)
    hit = (idx == slot)[None, None, :, None]

    new_scales = None
    if cache_scales is not None:                        # int8 cache
        kq, ks = quantize_kv(kh)
        vq, vs = quantize_kv(vh)
        new_k = jnp.where(hit, kq, cache_k)
        new_v = jnp.where(hit, vq, cache_v)
        hit2 = (idx == slot)[None, None, :]
        nks = jnp.where(hit2, ks, cache_scales[0])
        nvs = jnp.where(hit2, vs, cache_scales[1])
        new_scales = (nks, nvs)
        k_use = new_k.astype(jnp.bfloat16) * nks[..., None].astype(jnp.bfloat16)
        v_use = new_v.astype(jnp.bfloat16) * nvs[..., None].astype(jnp.bfloat16)
    else:
        new_k = jnp.where(hit, kh.astype(cache_k.dtype), cache_k)
        new_v = jnp.where(hit, vh.astype(cache_v.dtype), cache_v)
        k_use, v_use = new_k, new_v

    if window > 0:
        valid = (idx <= slot) | (pos >= s_cache)        # ring buffer occupancy
    else:
        valid = idx <= pos
    mask = jnp.broadcast_to(valid[None, :], (B, s_cache))
    o = ops.decode_attention(qh, k_use, v_use, mask, scale=softmax_scale(cfg))
    o = o.transpose(0, 2, 1, 3).reshape(B, 1, nh * hd)
    out = o @ p["w_o"]
    if cache_scales is not None:
        return out, new_k, new_v, new_scales
    return out, new_k, new_v


# ---------------------------------------------------------------------------
# cross attention (whisper decoder / llama-vision)
# ---------------------------------------------------------------------------

def init_cross_attention(b: ParamBuilder, name: str, cfg: ModelConfig, gated: bool = False):
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    sub = b.sub(name)
    sub.param("w_q", (cfg.d_model, nh * hd), (None, "heads"))
    sub.param("w_k", (cfg.d_model, nkv * hd), (None, "kv_heads"))
    sub.param("w_v", (cfg.d_model, nkv * hd), (None, "kv_heads"))
    sub.param("w_o", (nh * hd, cfg.d_model), ("heads", None))
    if gated:
        sub.param("gate", (1,), (None,), init="zeros", dtype=jnp.float32)


def cross_kv(p, memory: jax.Array, cfg: ModelConfig):
    """Precompute cross-attention K/V from encoder/vision memory (B, M, D)."""
    B, M, _ = memory.shape
    nkv, hd = cfg.num_kv_heads, cfg.head_dim_
    k = (memory @ p["w_k"]).reshape(B, M, nkv, hd).transpose(0, 2, 1, 3)
    v = (memory @ p["w_v"]).reshape(B, M, nkv, hd).transpose(0, 2, 1, 3)
    return k, v


def apply_cross_attention(p, x: jax.Array, k: jax.Array, v: jax.Array, cfg: ModelConfig):
    """x: (B, S, D) queries; k/v: (B, Hkv, M, hd) precomputed memory KV."""
    B, S, _ = x.shape
    nh, hd = cfg.num_heads, cfg.head_dim_
    q = (x @ p["w_q"]).reshape(B, S, nh, hd).transpose(0, 2, 1, 3)
    q = shard(q, "batch", "heads", None, None)
    M = k.shape[2]
    mask = jnp.ones((B, M), bool)
    if S == 1:
        o = ops.decode_attention(q, k, v, mask)
    else:
        rep = nh // k.shape[1]
        kf = jnp.repeat(k, rep, axis=1)
        vf = jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf.astype(jnp.float32))
        s = s / math.sqrt(hd)
        pw = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", pw, vf.astype(jnp.float32)).astype(x.dtype)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, nh * hd)
    out = o @ p["w_o"]
    if "gate" in p:
        out = out * jnp.tanh(p["gate"]).astype(out.dtype)
    return out


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------

def init_embed(b: ParamBuilder, cfg: ModelConfig):
    b.param("embed", (cfg.padded_vocab, cfg.d_model), ("vocab", None), init="embedding")
    if not cfg.tie_embeddings:
        b.param("lm_head", (cfg.d_model, cfg.padded_vocab), (None, "vocab"))


def embed_tokens(params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = params["embed"].astype(cfg.jnp_dtype)[tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return shard(x, "batch", None, None)


def unembed(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ w.astype(x.dtype)
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    if cfg.padded_vocab != cfg.vocab_size:  # mask padding tail
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, jnp.asarray(-1e30, logits.dtype))
    return shard(logits, "batch", None, "vocab")
