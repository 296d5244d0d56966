"""Mixture-of-Experts FFN with expert parallelism.

Experts are sharded on the "model" mesh axis (expert parallelism). Token
activations are sharded on the batch axes and *replicated* across the model
axis, so each model shard dispatches every token but computes only its local
expert slice; partial outputs are summed with one ``psum`` over "model" per
MoE layer.  Dispatch is sort-based (argsort by expert id + capacity clip) —
no (tokens x experts) one-hot matmuls, so compiled FLOPs reflect *active*
expert compute (correct MoE roofline).

Off-mesh (CPU smoke tests) the same core runs locally with E_local == E and
no collective.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.utils.params import ParamBuilder
from repro.utils.sharding import current_rules


def init_moe(b: ParamBuilder, name: str, cfg: ModelConfig):
    sub = b.sub(name)
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff_expert
    sub.param("router", (D, E), (None, None), dtype=jnp.float32)
    sub.param("w_in", (E, D, 2 * F), ("experts", None, None))
    sub.param("w_out", (E, F, D), ("experts", None, None))
    if cfg.num_shared_experts:
        Fs = F * cfg.num_shared_experts
        sub.param("w_shared_up", (D, Fs), (None, "ff"))
        sub.param("w_shared_gate", (D, Fs), (None, "ff"))
        sub.param("w_shared_out", (Fs, D), ("ff", None))


def _dispatch_compute(x, router_w, w_in, w_out, *, top_k, e_lo, num_experts,
                      e_local, capacity, axis_name):
    """Core MoE on local token shard x: (T, D). Returns (y (T, D), aux (T,))."""
    T, D = x.shape
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, top_k)                          # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # flat assignment list, token-major
    tok_idx = jnp.repeat(jnp.arange(T), top_k)                          # (T*k,)
    expert = top_i.reshape(-1)                                          # (T*k,)
    weight = top_w.reshape(-1)

    local_e = expert - e_lo
    sel = (local_e >= 0) & (local_e < e_local)
    key = jnp.where(sel, local_e, e_local)                              # e_local == drop bucket
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    # position within each expert's contiguous run
    first = jnp.searchsorted(key_s, key_s, side="left")
    pos = jnp.arange(T * top_k) - first
    slot = jnp.where((key_s < e_local) & (pos < capacity),
                     key_s * capacity + pos, e_local * capacity)        # last = drop slot

    xs = x[tok_idx[order]]                                              # (T*k, D)
    buf = jnp.zeros((e_local * capacity + 1, D), x.dtype).at[slot].set(xs)
    buf = buf[:-1].reshape(e_local, capacity, D)

    h = jnp.einsum("ecd,edf->ecf", buf, w_in.astype(x.dtype))
    u, g = jnp.split(h, 2, axis=-1)
    h = u * jax.nn.silu(g)
    out = jnp.einsum("ecf,efd->ecd", h, w_out.astype(x.dtype))

    out_flat = jnp.concatenate(
        [out.reshape(e_local * capacity, D), jnp.zeros((1, D), x.dtype)], axis=0
    )
    y_sorted = out_flat[slot]                                           # (T*k, D)
    y_assign = y_sorted[jnp.argsort(order)]                             # undo sort
    y = (y_assign.reshape(T, top_k, D)
         * weight.reshape(T, top_k, 1).astype(x.dtype)).sum(axis=1)

    if axis_name is not None:
        y = jax.lax.psum(y, axis_name)

    # Switch-style load-balance aux: E * sum_e f_e * p_e, as per-token shares.
    # Uses global expert ids (identical across model shards; no psum needed).
    me = jnp.zeros((num_experts,), jnp.float32).at[expert].add(1.0) / (T * top_k)
    ce = probs.mean(axis=0)
    aux = jnp.full((T,), num_experts * jnp.sum(me * ce), jnp.float32)
    return y, aux


def apply_moe_2d(p, x: jax.Array, cfg: ModelConfig):
    """Weight-resident 2D expert parallelism (decode regime).

    Expert stacks stay sharded (experts x model, hidden x data) — 256-way,
    never gathered; instead the *activations* (tiny at decode batch sizes)
    move: token slices are resharded token->feature (all-to-all), partial
    expert matmuls are psum'd over the data axis, and outputs are sliced
    back to batch sharding. Per-layer wire cost is a few MB instead of the
    multi-GB weight gathers ZeRO-style FSDP would need.
    """
    B, S, D = x.shape
    T = B * S
    E, k, F = cfg.num_experts, cfg.top_k, cfg.d_ff_expert
    rules = current_rules()
    assert rules is not None and "model" in rules.mesh.axis_names
    mesh = rules.mesh
    msize = mesh.shape["model"]
    dsize = mesh.shape["data"]
    e_local = E // msize
    assert D % dsize == 0 and (2 * F) % dsize == 0
    xf = x.reshape(T, D)
    capacity = max(4, int(T * k / E * cfg.capacity_factor) + 1)

    def body(x_slice, rw_slice, wi, wo):
        # x_slice: (T, D/dsize); rw_slice: (D/dsize, E)
        # wi: (E_local, D/dsize, 2F); wo: (E_local, F/dsize, D)
        di = jax.lax.axis_index("data")
        mi = jax.lax.axis_index("model")
        logits = jax.lax.psum(
            x_slice.astype(jnp.float32) @ rw_slice.astype(jnp.float32), "data")
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

        tok_idx = jnp.repeat(jnp.arange(T), k)
        expert = top_i.reshape(-1)
        weight = top_w.reshape(-1)
        local_e = expert - mi * e_local
        sel = (local_e >= 0) & (local_e < e_local)
        key = jnp.where(sel, local_e, e_local)
        order = jnp.argsort(key, stable=True)
        key_s = key[order]
        first = jnp.searchsorted(key_s, key_s, side="left")
        pos = jnp.arange(T * k) - first
        slot = jnp.where((key_s < e_local) & (pos < capacity),
                         key_s * capacity + pos, e_local * capacity)

        xs = x_slice[tok_idx[order]]                       # (T*k, D/dsize)
        buf = jnp.zeros((e_local * capacity + 1, x_slice.shape[1]),
                        x.dtype).at[slot].set(xs)
        buf = buf[:-1].reshape(e_local, capacity, x_slice.shape[1])

        h = jnp.einsum("ecd,edf->ecf", buf, wi.astype(x.dtype))
        h = jax.lax.psum(h, "data")                        # (E_l, C, 2F) full
        u, g = jnp.split(h, 2, axis=-1)
        h = u * jax.nn.silu(g)                             # (E_l, C, F)
        f_loc = F // dsize
        h_slice = jax.lax.dynamic_slice_in_dim(h, di * f_loc, f_loc, axis=2)
        out = jnp.einsum("ecf,efd->ecd", h_slice, wo.astype(x.dtype))
        out = jax.lax.psum(out, "data")                    # (E_l, C, D) full

        out_flat = jnp.concatenate(
            [out.reshape(e_local * capacity, D), jnp.zeros((1, D), x.dtype)], 0)
        y_sorted = out_flat[slot]
        y_assign = y_sorted[jnp.argsort(order)]
        y = (y_assign.reshape(T, k, D)
             * weight.reshape(T, k, 1).astype(x.dtype)).sum(axis=1)
        y = jax.lax.psum(y, "model")                       # (T, D) full
        t_loc = T // dsize
        y_local = jax.lax.dynamic_slice_in_dim(y, di * t_loc, t_loc, axis=0)
        me = jnp.zeros((E,), jnp.float32).at[expert].add(1.0) / (T * k)
        aux = jnp.full((t_loc,), E * jnp.sum(me * probs.mean(0)), jnp.float32)
        return y_local, aux

    y, aux = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "data"), P("data", None),
                  P("model", "data", None), P("model", "data", None)),
        out_specs=(P("data", None), P("data")),
        check_vma=False,
    )(xf, p["router"], p["w_in"], p["w_out"])
    out = y.reshape(B, S, D)
    if cfg.num_shared_experts:
        h = (xf @ p["w_shared_up"]) * jax.nn.silu(xf @ p["w_shared_gate"])
        out = out + (h @ p["w_shared_out"]).reshape(B, S, D)
    return out, aux


def apply_moe(p, x: jax.Array, cfg: ModelConfig, impl: str = "auto"):
    """x: (B, S, D) -> (out (B, S, D), aux_loss_per_token (B*S,))."""
    if impl == "2d":
        return apply_moe_2d(p, x, cfg)
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    E, k = cfg.num_experts, cfg.top_k
    rules = current_rules()
    if rules is not None and "model" in rules.mesh.axis_names:
        mesh = rules.mesh
        # expert-parallel axes from the logical rules: default ("model",);
        # decode may use 2D expert parallelism ("data","model") so the 1T
        # expert stacks shard over every chip.
        eaxes = rules.rules.get("experts") or ("model",)
        if isinstance(eaxes, str):
            eaxes = (eaxes,)
        eaxes = tuple(a for a in eaxes if a in mesh.axis_names)
        msize = math.prod(mesh.shape[a] for a in eaxes)
        assert E % msize == 0, f"experts {E} % expert-parallel size {msize}"
        e_local = E // msize
        batch_axes = tuple(a for a in ("pod", "data")
                           if a in mesh.axis_names and a not in eaxes)
        # drop batch axes that don't divide the token count (e.g. batch=1
        # long-context decode): those shards run replicated instead
        while batch_axes and (B * S) % math.prod(
                mesh.shape[a] for a in batch_axes) != 0:
            batch_axes = batch_axes[1:]
        t_local = (B * S) // math.prod(mesh.shape[a] for a in batch_axes) \
            if batch_axes else B * S
        capacity = max(4, int(t_local * k / E * cfg.capacity_factor) + 1)

        def body(xl, rw, wi, wo):
            e_idx = jnp.zeros((), jnp.int32)
            for a in eaxes:
                e_idx = e_idx * mesh.shape[a] + jax.lax.axis_index(a)
            return _dispatch_compute(
                xl, rw, wi, wo, top_k=k, e_lo=e_idx * e_local, num_experts=E,
                e_local=e_local, capacity=capacity, axis_name=eaxes)

        y, aux = shard_map(
            body, mesh=mesh,
            in_specs=(P(batch_axes, None), P(None, None),
                      P(eaxes, None, None), P(eaxes, None, None)),
            out_specs=(P(batch_axes, None), P(batch_axes)),
            check_vma=False,
        )(xf, p["router"], p["w_in"], p["w_out"])
    else:
        capacity = max(4, int(B * S * k / E * cfg.capacity_factor) + 1)
        y, aux = _dispatch_compute(
            xf, p["router"], p["w_in"], p["w_out"], top_k=k, e_lo=0,
            num_experts=E, e_local=E, capacity=capacity, axis_name=None)

    out = y.reshape(B, S, D)
    if cfg.num_shared_experts:
        h = (xf @ p["w_shared_up"]) * jax.nn.silu(xf @ p["w_shared_gate"])
        out = out + (h @ p["w_shared_out"]).reshape(B, S, D)
    return out, aux
