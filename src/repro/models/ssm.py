"""Mamba-2 block (SSD, arXiv:2405.21060).

Projections -> causal depthwise conv on (x, B, C), with a bias where
``ssm_conv_bias`` -> chunked SSD scan (Pallas kernel on TPU, chunked jnp
elsewhere) -> gated RMSNorm -> out proj. Decode carries (conv window, SSM
state) — O(1) per token, which is what makes ``long_500k`` native for this
family. A full-sequence pass ends with the same two, so a prefill leaves
them in the decode cache.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.utils.params import ParamBuilder
from repro.utils.sharding import shard


def ssm_dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    d_bc = cfg.ssm_ngroups * cfg.ssm_state
    return d_inner, n_heads, cfg.ssm_ngroups, d_bc


def init_ssm(b: ParamBuilder, name: str, cfg: ModelConfig):
    d_inner, H, G, d_bc = ssm_dims(cfg)
    K = cfg.ssm_conv
    sub = b.sub(name)
    sub.param("w_z", (cfg.d_model, d_inner), (None, "ff"))
    sub.param("w_x", (cfg.d_model, d_inner), (None, "ff"))
    sub.param("w_b", (cfg.d_model, d_bc), (None, None))
    sub.param("w_c", (cfg.d_model, d_bc), (None, None))
    sub.param("w_dt", (cfg.d_model, H), (None, None))
    sub.param("dt_bias", (H,), (None,), init="zeros", dtype=jnp.float32)
    sub.param("a_log", (H,), (None,), init="zeros", dtype=jnp.float32)
    sub.param("d_skip", (H,), (None,), init="ones", dtype=jnp.float32)
    sub.param("conv_x", (K, d_inner), (None, "ff"), scale=0.5)
    sub.param("conv_b", (K, d_bc), (None, None), scale=0.5)
    sub.param("conv_c", (K, d_bc), (None, None), scale=0.5)
    if cfg.ssm_conv_bias:       # over (x, B, C), in that order
        sub.param("conv_bias", (d_inner + 2 * d_bc,), (None,), init="zeros")
    sub.param("norm", (d_inner,), (None,), init="ones", dtype=jnp.float32)
    sub.param("w_out", (d_inner, cfg.d_model), ("ff", None))


def _causal_depthwise(x: jax.Array, w: jax.Array, init_state: jax.Array | None = None):
    """x: (B, L, C); w: (K, C). Left-padded causal depthwise conv.

    ``init_state``: (B, K-1, C) carried context (decode continuity).
    Returns (y (B, L, C), new_state (B, K-1, C)).
    """
    K = w.shape[0]
    if init_state is None:
        init_state = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([init_state, x], axis=1)
    y = sum(xp[:, i : i + x.shape[1], :] * w[i] for i in range(K))
    new_state = xp[:, -(K - 1):, :] if K > 1 else init_state
    return y, new_state


def apply_ssm(p, x: jax.Array, cfg: ModelConfig):
    """Full-sequence SSD from a zero state. x: (B, L, D). Returns (out,
    {"conv": the last ``ssm_conv - 1`` conv inputs, "ssm": the final state})
    — what ``apply_ssm_decode`` carries after the same L positions. A length
    above the chunk and not a multiple of it is padded with dt = 0, which
    leaves the state as it was."""
    B, L, D = x.shape
    d_inner, H, G, d_bc = ssm_dims(cfg)
    P_dim = cfg.ssm_head_dim
    N = cfg.ssm_state
    K = cfg.ssm_conv

    z = x @ p["w_z"]
    xs = x @ p["w_x"]
    bm = x @ p["w_b"]
    cm = x @ p["w_c"]
    dt_raw = x @ p["w_dt"]

    conv_in = jnp.concatenate([xs, bm, cm], axis=-1)
    conv_w = jnp.concatenate([p["conv_x"], p["conv_b"], p["conv_c"]], axis=-1)
    conv_out, new_conv = _causal_depthwise(conv_in, conv_w)
    if "conv_bias" in p:
        conv_out = conv_out + p["conv_bias"]
    conv_out = jax.nn.silu(conv_out)
    xs = conv_out[..., :d_inner]
    bm = conv_out[..., d_inner : d_inner + d_bc]
    cm = conv_out[..., d_inner + d_bc :]

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    xh = xs.reshape(B, L, H, P_dim)
    xh = shard(xh, "batch", None, "heads", None)
    bmr = bm.reshape(B, L, G, N)
    cmr = cm.reshape(B, L, G, N)
    chunk = min(cfg.ssm_chunk, L)
    pad = -L % chunk
    if pad:
        def tail(t):
            return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        y, h_fin = ops.ssd(tail(xh), tail(dt), a, tail(bmr), tail(cmr), chunk=chunk)
        y = y[:, :L]
    else:
        y, h_fin = ops.ssd(xh, dt, a, bmr, cmr, chunk=chunk)
    y = y + p["d_skip"].astype(y.dtype)[None, None, :, None] * xh
    y = y.reshape(B, L, d_inner)
    y = y * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    y = (yf * jax.lax.rsqrt(jnp.mean(jnp.square(yf), -1, keepdims=True) + cfg.norm_eps)
         * p["norm"]).astype(x.dtype)
    y = shard(y, "batch", None, "ff")
    out = y @ p["w_out"]
    return out, {"conv": new_conv, "ssm": h_fin}


def apply_ssm_decode(p, x: jax.Array, cfg: ModelConfig, state):
    """One-token SSD step. x: (B, 1, D); state: {"conv": (B,K-1,C), "ssm": (B,H,P,N)}."""
    B = x.shape[0]
    d_inner, H, G, d_bc = ssm_dims(cfg)
    P_dim, N, K = cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv

    xt = x[:, 0, :]
    z = xt @ p["w_z"]
    xs = xt @ p["w_x"]
    bm = xt @ p["w_b"]
    cm = xt @ p["w_c"]
    dt_raw = xt @ p["w_dt"]

    conv_in = jnp.concatenate([xs, bm, cm], axis=-1)          # (B, C)
    window = jnp.concatenate([state["conv"], conv_in[:, None, :]], axis=1)  # (B, K, C)
    conv_w = jnp.concatenate([p["conv_x"], p["conv_b"], p["conv_c"]], axis=-1)
    conv_out = jnp.einsum("bkc,kc->bc", window, conv_w)
    if "conv_bias" in p:
        conv_out = conv_out + p["conv_bias"]
    conv_out = jax.nn.silu(conv_out)
    new_conv = window[:, 1:, :]

    xs = conv_out[:, :d_inner]
    bm = conv_out[:, d_inner : d_inner + d_bc].reshape(B, G, N)
    cm = conv_out[:, d_inner + d_bc :].reshape(B, G, N)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    xh = xs.reshape(B, H, P_dim)
    y, h_new = ops.ssd_decode_step(xh, dt, a, bm, cm, state["ssm"])
    y = y + p["d_skip"].astype(y.dtype)[None, :, None] * xh
    y = y.reshape(B, d_inner)
    y = y * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    y = (yf * jax.lax.rsqrt(jnp.mean(jnp.square(yf), -1, keepdims=True) + cfg.norm_eps)
         * p["norm"]).astype(x.dtype)
    out = (y @ p["w_out"])[:, None, :]
    return out, {"conv": new_conv, "ssm": h_new}
