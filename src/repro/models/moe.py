"""Mixture-of-Experts FFN with expert parallelism.

Experts are sharded on the "model" mesh axis (expert parallelism). Token
activations are sharded on the batch axes and *replicated* across the model
axis, so each model shard dispatches every token but computes only its local
expert slice; partial outputs are summed with one ``psum`` over "model" per
MoE layer.  Dispatch is sort-based (argsort by expert id) — no (tokens x
experts) one-hot matmuls, so compiled FLOPs reflect *active* expert compute
(correct MoE roofline). Training clips each expert at its capacity;
serving (``serve_moe``) drops nothing: a grouped product
(``jax.lax.ragged_dot``) over the held experts' sorted assignments.

A layer may hold a share of the routed experts (``experts_held`` from
``first_expert_held``, one chip's share of a deployment): the router still
scores all ``num_experts`` and the layer adds only its own experts' part.
Off-mesh (CPU smoke tests, one chip) the same core runs locally with no
collective.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.utils.params import ParamBuilder
from repro.utils.sharding import current_rules


def init_moe(b: ParamBuilder, name: str, cfg: ModelConfig):
    """The router over all ``num_experts``; the routed experts held here."""
    sub = b.sub(name)
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff_expert
    sub.param("router", (D, E), (None, None), dtype=jnp.float32)
    sub.param("w_in", (cfg.num_experts_held, D, 2 * F), ("experts", None, None))
    sub.param("w_out", (cfg.num_experts_held, F, D), ("experts", None, None))
    if cfg.num_shared_experts:
        Fs = F * cfg.num_shared_experts
        sub.param("w_shared_up", (D, Fs), (None, "ff"))
        sub.param("w_shared_gate", (D, Fs), (None, "ff"))
        sub.param("w_shared_out", (Fs, D), ("ff", None))


def router_logits(x, router_w):
    """The router's scores before the softmax, in float32 at full precision
    (a float32 product on the TPU otherwise takes one bfloat16 pass)."""
    return jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def route(logits, cfg: ModelConfig):
    """Softmax scores over all experts and each token's top-k: ``(probs (T,
    E), weights (T, k), experts (T, k))``. Group-limited greedy where
    ``n_group`` > 1: the experts form ``n_group`` contiguous groups, a token
    keeps the ``topk_group`` groups whose best score is highest and picks
    its top-k inside them. Weights are renormalised where ``norm_topk_prob``,
    else scaled by ``routed_scaling_factor``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    scores = probs
    if cfg.n_group > 1:
        T, E = probs.shape
        best = probs.reshape(T, cfg.n_group, E // cfg.n_group).max(-1)   # (T, G)
        _, kept = jax.lax.top_k(best, cfg.topk_group)
        keep = (kept[:, :, None] == jnp.arange(cfg.n_group)).any(1)      # (T, G)
        scores = jnp.where(jnp.repeat(keep, E // cfg.n_group, axis=1), probs, 0.0)
    top_w, top_i = jax.lax.top_k(scores, cfg.top_k)
    if cfg.norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    elif cfg.routed_scaling_factor != 1.0:
        top_w = top_w * cfg.routed_scaling_factor
    return probs, top_w, top_i


def _counts(load, capacity):
    """``(routed, most on one expert, dropped)`` int32 from the assignments
    each held expert was given, ``load`` (E_local,), at ``capacity`` (None:
    no limit)."""
    done = load if capacity is None else jnp.minimum(load, capacity)
    return jnp.stack([done.sum(), done.max(), load.sum() - done.sum()]).astype(jnp.int32)


def add_counts(total, counts):
    """``total`` (3,) with the counts of more layers or steps (n, 3) added:
    assignments summed, the most on one expert the largest."""
    return jnp.stack([total[0] + counts[:, 0].sum(), jnp.maximum(total[1], counts[:, 1].max()),
                      total[2] + counts[:, 2].sum()])


def _dispatch_compute(x, router_w, w_in, w_out, cfg: ModelConfig, *, e_lo,
                      e_local, capacity, axis_name):
    """Core MoE on local token shard x: (T, D), for the ``e_local`` experts
    from ``e_lo`` held in ``w_in``/``w_out``. ``capacity`` None drops
    nothing (a grouped product over the held experts' sorted assignments),
    else each expert takes at most ``capacity`` tokens. Returns (y (T, D),
    aux (T,), counts (3,))."""
    T, D = x.shape
    top_k, num_experts = cfg.top_k, cfg.num_experts
    probs, top_w, top_i = route(router_logits(x, router_w), cfg)

    # flat assignment list, token-major
    tok_idx = jnp.repeat(jnp.arange(T), top_k)                          # (T*k,)
    expert = top_i.reshape(-1)                                          # (T*k,)
    weight = top_w.reshape(-1)

    local_e = expert - e_lo
    sel = (local_e >= 0) & (local_e < e_local)
    key = jnp.where(sel, local_e, e_local)                              # e_local == not held
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    load = jnp.zeros((e_local + 1,), jnp.int32).at[key].add(1)[:e_local]
    xs = x[tok_idx[order]]                                              # (T*k, D)

    if capacity is None:
        h = jax.lax.ragged_dot(xs, w_in.astype(x.dtype), load)
        u, g = jnp.split(h, 2, axis=-1)
        y_sorted = jax.lax.ragged_dot(u * jax.nn.silu(g), w_out.astype(x.dtype), load)
        y_sorted = jnp.where((key_s < e_local)[:, None], y_sorted, 0)  # rows not held
    else:
        # position within each expert's contiguous run
        first = jnp.searchsorted(key_s, key_s, side="left")
        pos = jnp.arange(T * top_k) - first
        slot = jnp.where((key_s < e_local) & (pos < capacity),
                         key_s * capacity + pos, e_local * capacity)    # last = drop slot
        buf = jnp.zeros((e_local * capacity + 1, D), x.dtype).at[slot].set(xs)
        buf = buf[:-1].reshape(e_local, capacity, D)

        h = jnp.einsum("ecd,edf->ecf", buf, w_in.astype(x.dtype))
        u, g = jnp.split(h, 2, axis=-1)
        h = u * jax.nn.silu(g)
        out = jnp.einsum("ecf,efd->ecd", h, w_out.astype(x.dtype))

        out_flat = jnp.concatenate(
            [out.reshape(e_local * capacity, D), jnp.zeros((1, D), x.dtype)], axis=0
        )
        y_sorted = out_flat[slot]                                       # (T*k, D)
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
    return y, aux, _counts(load, capacity)


def apply_moe_2d(p, x: jax.Array, cfg: ModelConfig):
    """Weight-resident 2D expert parallelism (decode regime).

    Expert stacks stay sharded (experts x model, hidden x data) — 256-way,
    never gathered; instead the *activations* (tiny at decode batch sizes)
    move: token slices are resharded token->feature (all-to-all), partial
    expert matmuls are psum'd over the data axis, and outputs are sliced
    back to batch sharding. Per-layer wire cost is a few MB instead of the
    multi-GB weight gathers ZeRO-style FSDP would need. Experts take at most
    their capacity, serving included; the drops show in the counts.
    Returns (out, aux, counts).
    """
    B, S, D = x.shape
    T = B * S
    E, k, F = cfg.num_experts, cfg.top_k, cfg.d_ff_expert
    rules = current_rules()
    assert rules is not None and "model" in rules.mesh.axis_names
    mesh = rules.mesh
    msize = mesh.shape["model"]
    dsize = mesh.shape["data"]
    e_local = cfg.num_experts_held // msize
    assert D % dsize == 0 and (2 * F) % dsize == 0
    xf = x.reshape(T, D)
    capacity = max(4, int(T * k / E * cfg.capacity_factor) + 1)

    def body(x_slice, rw_slice, wi, wo):
        # x_slice: (T, D/dsize); rw_slice: (D/dsize, E)
        # wi: (E_local, D/dsize, 2F); wo: (E_local, F/dsize, D)
        di = jax.lax.axis_index("data")
        mi = jax.lax.axis_index("model")
        logits = jax.lax.psum(router_logits(x_slice, rw_slice), "data")
        probs, top_w, top_i = route(logits, cfg)

        tok_idx = jnp.repeat(jnp.arange(T), k)
        expert = top_i.reshape(-1)
        weight = top_w.reshape(-1)
        local_e = expert - (cfg.first_expert_held + mi * e_local)
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
        load = jnp.zeros((e_local + 1,), jnp.int32).at[key].add(1)[:e_local]
        return y_local, aux, _reduce_counts(_counts(load, capacity), ("model",))

    y, aux, counts = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "data"), P("data", None),
                  P("model", "data", None), P("model", "data", None)),
        out_specs=(P("data", None), P("data"), P()),
        check_vma=False,
    )(xf, p["router"], p["w_in"], p["w_out"])
    return _add_shared(p, xf, y, cfg).reshape(B, S, D), aux, counts


def _reduce_counts(counts, axes):
    """Counts of every shard over ``axes``: assignments summed, the most on
    one expert the largest."""
    total = jax.lax.psum(counts, axes)
    return total.at[1].set(jax.lax.pmax(counts[1], axes))


def _add_shared(p, xf, y, cfg: ModelConfig):
    """The routed part ``y`` (T, D) plus the shared experts' output."""
    if not cfg.num_shared_experts:
        return y
    h = (xf @ p["w_shared_up"]) * jax.nn.silu(xf @ p["w_shared_gate"])
    return y + h @ p["w_shared_out"]


def _moe(p, x: jax.Array, cfg: ModelConfig, impl: str, dropless: bool):
    """x: (B, S, D) -> (out (B, S, D), aux_loss_per_token (B*S,), counts (3,))."""
    if impl == "2d":
        return apply_moe_2d(p, x, cfg)
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    E, k, held = cfg.num_experts, cfg.top_k, cfg.num_experts_held
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
        assert held % msize == 0, f"experts {held} % expert-parallel size {msize}"
        e_local = held // msize
        batch_axes = tuple(a for a in ("pod", "data")
                           if a in mesh.axis_names and a not in eaxes)
        # drop batch axes that don't divide the token count (e.g. batch=1
        # long-context decode): those shards run replicated instead
        while batch_axes and (B * S) % math.prod(
                mesh.shape[a] for a in batch_axes) != 0:
            batch_axes = batch_axes[1:]
        t_local = (B * S) // math.prod(mesh.shape[a] for a in batch_axes) \
            if batch_axes else B * S
        capacity = None if dropless else max(4, int(t_local * k / E * cfg.capacity_factor) + 1)

        def body(xl, rw, wi, wo):
            e_idx = jnp.zeros((), jnp.int32)
            for a in eaxes:
                e_idx = e_idx * mesh.shape[a] + jax.lax.axis_index(a)
            y, aux, counts = _dispatch_compute(
                xl, rw, wi, wo, cfg, e_lo=cfg.first_expert_held + e_idx * e_local,
                e_local=e_local, capacity=capacity, axis_name=eaxes)
            return y, aux, _reduce_counts(counts, eaxes + batch_axes)

        y, aux, counts = shard_map(
            body, mesh=mesh,
            in_specs=(P(batch_axes, None), P(None, None),
                      P(eaxes, None, None), P(eaxes, None, None)),
            out_specs=(P(batch_axes, None), P(batch_axes), P()),
            check_vma=False,
        )(xf, p["router"], p["w_in"], p["w_out"])
    else:
        capacity = None if dropless else max(4, int(B * S * k / E * cfg.capacity_factor) + 1)
        y, aux, counts = _dispatch_compute(
            xf, p["router"], p["w_in"], p["w_out"], cfg, e_lo=cfg.first_expert_held,
            e_local=held, capacity=capacity, axis_name=None)
    return _add_shared(p, xf, y, cfg).reshape(B, S, D), aux, counts


def apply_moe(p, x: jax.Array, cfg: ModelConfig, impl: str = "auto"):
    """Training: experts take at most their capacity of tokens.
    x: (B, S, D) -> (out (B, S, D), aux_loss_per_token (B*S,))."""
    out, aux, _ = _moe(p, x, cfg, impl, dropless=False)
    return out, aux


def serve_moe(p, x: jax.Array, cfg: ModelConfig, impl: str = "auto"):
    """Serving: no token is dropped (but on the ``"2d"`` path). x: (B, S, D)
    -> (out (B, S, D), counts (3,) int32: assignments the held experts
    computed, the most one of them took, assignments dropped)."""
    out, _, counts = _moe(p, x, cfg, impl, dropless=True)
    return out, counts
