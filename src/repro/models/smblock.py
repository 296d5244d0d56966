"""Explicit Megatron-SP dense transformer block (shard_map).

The GSPMD sequence-parallel path (§Perf pair 1, iteration 4/6) emits
all-reduce + re-shard + all-gather per sublayer because the partitioner
fails to fuse partial-sum dots into reduce-scatters. This block writes the
collectives by hand:

  per sublayer:  all_gather(x, model)  ->  local compute on H/16 heads or
                 FF/16 hidden  ->  psum_scatter(out, model)

so the residual stream stays sequence-sharded end-to-end: exactly 2 AG +
2 RS of (B_l, S, D)-sized tensors per layer in fwd (the transpose pair in
bwd), i.e. the same wire bytes as plain tensor-parallel all-reduces but with
16x smaller saved activations. Differentiable (shard_map transposes AG <->
psum_scatter automatically); used for the dense family under
``model.block_impl = "shardmap"`` (dry-run opt ``smblock``).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models.layers import apply_rope


def _norm(scale, x, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def dense_block_shardmap(p, x: jax.Array, cfg: ModelConfig, mesh,
                         window: int = 0) -> jax.Array:
    """x: (B, S, D) sequence-sharded on "model". Returns same layout."""
    msize = mesh.shape["model"]
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    assert nh % msize == 0, "q heads must divide the model axis"
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    B, S, D = x.shape

    def body(x_l, ln1, wq, bq, wk, bk, wv, bv, wo, ln2, wup, wgate, wdown):
        # x_l: (B_l, S/m, D); wq: (D, H_l*hd); wk/wv: (D, KV*hd) replicated
        positions = jnp.arange(S)
        xf = jax.lax.all_gather(x_l, "model", axis=1, tiled=True)  # (B_l, S, D)
        h = _norm(ln1, xf, cfg.norm_eps)
        q = h @ wq
        k = h @ wk
        v = h @ wv
        q, k, v = q + bq, k + bk, v + bv
        bl = xf.shape[0]
        h_l = nh // msize
        q = q.reshape(bl, S, h_l, hd)
        k = k.reshape(bl, S, nkv, hd)
        v = v.reshape(bl, S, nkv, hd)
        # select this shard's kv heads (kv projections are computed fully —
        # they are small — then sliced to the local q-heads' groups)
        mi = jax.lax.axis_index("model")
        kidx = ((mi * h_l + jnp.arange(h_l)) * nkv) // nh
        k = jnp.take(k, kidx, axis=2)
        v = jnp.take(v, kidx, axis=2)
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_pct)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_pct)
        o = ops.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=True, window=window)
        o = o.transpose(0, 2, 1, 3).reshape(bl, S, -1)
        attn_partial = o @ wo                                   # partial over heads
        attn_out = jax.lax.psum_scatter(attn_partial, "model",
                                        scatter_dimension=1, tiled=True)
        x_l = x_l + attn_out.astype(x_l.dtype)

        xf2 = jax.lax.all_gather(x_l, "model", axis=1, tiled=True)
        h2 = _norm(ln2, xf2, cfg.norm_eps)
        hh = (h2 @ wup) * jax.nn.silu(h2 @ wgate)               # (B_l, S, FF/m)
        mlp_partial = hh @ wdown                                # partial over FF
        mlp_out = jax.lax.psum_scatter(mlp_partial, "model",
                                       scatter_dimension=1, tiled=True)
        return x_l + mlp_out.astype(x_l.dtype)

    attn = p["attn"]
    dt = x.dtype
    zq = attn.get("b_q", jnp.zeros((nh * hd,), dt))
    zk = attn.get("b_k", jnp.zeros((nkv * hd,), dt))
    zv = attn.get("b_v", jnp.zeros((nkv * hd,), dt))
    args = (
        x,
        p["ln1"]["scale"],
        attn["w_q"], zq, attn["w_k"], zk, attn["w_v"], zv, attn["w_o"],
        p["ln2"]["scale"],
        p["ffn"]["w_up"], p["ffn"]["w_gate"], p["ffn"]["w_out"],
    )
    in_specs = (
        P(batch_axes, "model", None),            # x: seq-sharded
        P(None),
        P(None, "model"), P("model"),
        P(None, None), P(None),
        P(None, None), P(None),
        P("model", None),
        P(None),
        P(None, "model"), P(None, "model"), P("model", None),
    )
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=P(batch_axes, "model", None),
                     check_vma=False)(*args)
