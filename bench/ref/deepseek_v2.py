"""Plain DeepSeek-V2 decoder in ``jax.numpy``: the reference the served tokens
are compared with.

Written from the DeepSeek-V2 architecture (``DeepseekV2ForCausalLM``,
arXiv 2405.04434): token embedding; per layer RMSNorm, multi-head latent
attention in its full (not absorbed) form, a residual add, RMSNorm, the
feed-forward part and a residual add; a final RMSNorm and an untied output
head. It imports nothing of the program. Float32 at highest precision.

- Attention: q = up(RMSNorm(down_q(x))) per head, 128 "nope" and 64 rope
  dims; the compressed latent c = RMSNorm(down_kv(x)[:512]) and one shared
  rope key down_kv(x)[512:]; k_nope and v are up-projections of c. Rope
  is YaRN (factor, original positions, beta_fast, beta_slow of the
  config's ``rope_scaling``), on the two halves of the rope dims; the
  softmax scale is mscale(factor, mscale_all_dim)^2 / sqrt(192). Causal.
- Feed-forward: layer 0 (``first_k_dense_replace``) a SwiGLU MLP of
  ``intermediate_size``; the others the shared experts (one SwiGLU MLP of
  ``n_shared_experts * moe_intermediate_size``) plus the routed experts'
  part. Routing: softmax over all routed experts' scores, group-limited
  greedy (the ``topk_group`` of ``n_group`` contiguous groups with the
  highest top score, then the top ``num_experts_per_tok`` inside them),
  weights not renormalised but scaled by ``routed_scaling_factor``. The
  routed part sums, over the experts held here (the config's
  ``n_routed_experts`` from ``first_routed_expert``), each expert's SwiGLU
  output times its weight for the tokens routed to it: every held expert
  is computed for every token and weighted by zero where not chosen.

The weights are read by the names of the served parameter tree: ``embed``,
``lm_head``, ``final_norm/scale``; stacked over layers,
``dense_blocks/...`` and ``blocks/...`` with ``{ln1,ln2}/scale``,
``attn/{w_dq,q_norm,w_uq,w_dkv,kv_norm,w_uk,w_uv,w_o}`` (``x @ w``
layout), ``ffn/{w_gate,w_up,w_out}`` (dense) or ``ffn/{router,w_in,w_out,
w_shared_gate,w_shared_up,w_shared_out}`` (``w_in`` holds each expert's up
then gate projection). Each layer's weights are taken up to float32 one
layer at a time; attention runs one sequence and one block of query rows
at a time, and the head one slice of the vocabulary at a time.

With ``quant="fp8"`` every matrix product takes its operands rounded to
8-bit floats with 4 exponent and 3 mantissa bits (e4m3; weights scaled
per tensor, activations per row, to the format's largest finite value):
the control, which ranks tokens in the nearest precision below the served
bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
FP8_MAX = 240.0      # the largest finite e4m3 value of lax.reduce_precision
Q_ROWS = 128         # query rows of one attention block
VOCAB_SLICES = 8     # slices of the head


def _round(x, per_row: bool, quant):
    """``x`` rounded to e4m3 after scaling; ``reduce_precision`` and not a
    pair of converts, which XLA may drop."""
    if quant is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True) if per_row else jnp.max(jnp.abs(x))
    s = FP8_MAX / jnp.maximum(amax, 1e-30)
    return lax.reduce_precision(x * s, exponent_bits=4, mantissa_bits=3) / s


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(config: dict) -> np.ndarray:
    """YaRN's inverse frequencies of the rope dims (``rope_scaling``)."""
    rs = config["rope_scaling"]
    dim, base = config["qk_rope_head_dim"], float(config["rope_theta"])
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    extrapolated = 1.0 - ramp
    return inter * (1 - extrapolated) + extra * extrapolated


def _dims(config: dict):
    rs = config["rope_scaling"]
    factor = float(rs["factor"])
    nope, rdim = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    scale = _mscale(factor, rs["mscale_all_dim"]) ** 2 / math.sqrt(nope + rdim)
    cos_sin = _mscale(factor, rs["mscale"]) / _mscale(factor, rs["mscale_all_dim"])
    return dict(
        heads=config["num_attention_heads"], nope=nope, rdim=rdim,
        vdim=config["v_head_dim"], kv_rank=config["kv_lora_rank"],
        vocab=config["vocab_size"], eps=float(config["rms_norm_eps"]),
        experts=config["published"]["n_routed_experts"], top_k=config["num_experts_per_tok"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        scaling=float(config["routed_scaling_factor"]),
        first=config["first_routed_expert"], held=config["n_routed_experts"],
        scale=scale, cos_sin=cos_sin, inv_freq=tuple(yarn_inv_freq(config)))


def logits(config: dict, params, tokens, start: int, count: int, quant=None):
    """Reference logits ``(n, count, vocab)`` at positions ``start`` to
    ``start + count - 1`` of ``tokens`` ``(n, S)``."""
    dims = tuple(sorted(_dims(config).items()))
    return _logits(dims, start, count, quant)(params, jnp.asarray(tokens))


def gaps(config: dict, params, tokens, served, prompt_len: int, quant=None):
    """Per generated position, how far the served token's logit lies below
    the reference's best: ``(n, N)`` float32, 0 where the served token is
    the reference's argmax.

    ``tokens``: ``(n, P + N - 1)`` prompt and served tokens but the last;
    ``served``: ``(n, N)``. With ``quant``, also returns the same gap of
    the token that the lower-precision forward ranks first.
    """
    dims = tuple(sorted(_dims(config).items()))
    fn = _gaps(dims, prompt_len, served.shape[1], quant)
    return fn(params, jnp.asarray(tokens), jnp.asarray(served))


def route(dims: dict, scores):
    """Routing weights ``(T, experts)`` of softmax ``scores``, zero where an
    expert was not chosen, and the chosen experts ``(T, top_k)``:
    group-limited greedy top-k, scaled."""
    T, E = scores.shape
    G = dims["n_group"]
    best = scores.reshape(T, G, E // G).max(-1)
    _, groups = lax.top_k(best, dims["topk_group"])
    in_kept = (jnp.arange(E)[None, None, :] // (E // G) == groups[:, :, None]).any(1)
    w, chosen = lax.top_k(jnp.where(in_kept, scores, 0.0), dims["top_k"])
    onehot = chosen[:, :, None] == jnp.arange(E)[None, None, :]
    return (onehot * w[:, :, None]).sum(1) * dims["scaling"], chosen


def routes(config: dict, params, tokens, start: int, count: int):
    """The experts the reference routes each of ``count`` positions from
    ``start`` to, per MoE layer: ``(layers, n, count, top_k)``, sorted."""
    dims = tuple(sorted(_dims(config).items()))
    return _routes(dims, start, count)(params, jnp.asarray(tokens))


@functools.lru_cache(maxsize=None)
def _routes(dims, start: int, count: int):
    forward = _forward(dict(dims))

    def run(params, tokens):
        chosen = forward(params, tokens, start, count, None, routes=True)
        return jnp.sort(chosen[:, :, start:start + count], axis=-1)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _logits(dims, start: int, count: int, quant):
    forward = _forward(dict(dims))
    return jax.jit(lambda params, tokens: forward(params, tokens, start, count, quant))


@functools.lru_cache(maxsize=None)
def _gaps(dims, prompt_len: int, new: int, quant):
    forward = _forward(dict(dims))

    def run(params, tokens, served):
        logits = forward(params, tokens, prompt_len - 1, new, None)
        best = logits.max(-1)
        gap = best - jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
        if quant is None:
            return gap
        top = forward(params, tokens, prompt_len - 1, new, quant).argmax(-1)
        return gap, best - jnp.take_along_axis(logits, top[..., None], -1)[..., 0]

    return jax.jit(run)


def _forward(d: dict):
    H, nope, rdim, vd, kr = d["heads"], d["nope"], d["rdim"], d["vdim"], d["kv_rank"]
    inv_freq = jnp.asarray(np.asarray(d["inv_freq"]), jnp.float32)

    def mm(x, w, q):
        return jnp.matmul(_round(x, True, q), _round(w, False, q), precision=HIGHEST)

    def rms(x, scale):
        return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + d["eps"]) * scale

    def rope(x, pos):                      # x: (S, ..., rdim), halves rotated
        ang = pos[:, None].astype(jnp.float32) * inv_freq[None, :]
        ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
        cos = jnp.concatenate([jnp.cos(ang)] * 2, -1) * d["cos_sin"]
        sin = jnp.concatenate([jnp.sin(ang)] * 2, -1) * d["cos_sin"]
        half = jnp.concatenate([-x[..., rdim // 2:], x[..., :rdim // 2]], -1)
        return x * cos + half * sin

    def swiglu(h, w_gate, w_up, w_down, q):
        return mm(jax.nn.silu(mm(h, w_gate, q)) * mm(h, w_up, q), w_down, q)

    def attention(a, h, q):                # one sequence: h (S, D)
        S = h.shape[0]
        pos = jnp.arange(S)
        qh = mm(rms(mm(h, a["w_dq"], q), a["q_norm"]), a["w_uq"], q).reshape(S, H, nope + rdim)
        qh = jnp.concatenate([qh[..., :nope], rope(qh[..., nope:], pos)], -1)
        kv = mm(h, a["w_dkv"], q)
        c = rms(kv[:, :kr], a["kv_norm"])
        k_pe = rope(kv[:, kr:], pos)                                     # (S, rdim)
        k = jnp.concatenate([mm(c, a["w_uk"], q).reshape(S, H, nope),
                             jnp.broadcast_to(k_pe[:, None, :], (S, H, rdim))], -1)
        v = mm(c, a["w_uv"], q).reshape(S, H, vd)
        k, v = _round(k, True, q), _round(v, False, q)
        rows = -(-S // Q_ROWS) * Q_ROWS
        qp = jnp.pad(qh, ((0, rows - S), (0, 0), (0, 0))).reshape(rows // Q_ROWS, Q_ROWS, H, -1)

        def block(args):                   # Q_ROWS query rows
            i, qb = args
            s = jnp.einsum("qhd,khd->hqk", _round(qb, True, q), k, precision=HIGHEST) * d["scale"]
            causal = i * Q_ROWS + jnp.arange(Q_ROWS)[:, None] >= pos[None, :]
            w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khv->qhv", _round(w, True, q), v, precision=HIGHEST)

        o = lax.map(block, (jnp.arange(rows // Q_ROWS), qp)).reshape(rows, H * vd)[:S]
        return mm(o, a["w_o"], q)

    def routed(f, h, q):                   # h (T, D): the held experts' part
        weights, chosen = route(d, jax.nn.softmax(mm(h, f["router"], q), axis=-1))
        held = lax.dynamic_slice_in_dim(weights, d["first"], d["held"], axis=1)

        def expert(y, args):
            w_in, w_out, gate = args
            up, g = jnp.split(mm(h, w_in, q), 2, axis=-1)
            return y + gate[:, None] * mm(up * jax.nn.silu(g), w_out, q), None

        y, _ = lax.scan(expert, jnp.zeros_like(h), (f["w_in"], f["w_out"], held.T))
        return y, chosen

    def layer(moe, q):
        def body(x, p):                    # x (n, S, D)
            p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
            n, S, D = x.shape
            h = rms(x, p["ln1"]["scale"])
            x = x + lax.map(lambda hs: attention(p["attn"], hs, q), h)
            h = rms(x, p["ln2"]["scale"]).reshape(n * S, D)
            f = p["ffn"]
            chosen = None
            if moe:
                out, chosen = routed(f, h, q)
                out = out + swiglu(h, f["w_shared_gate"], f["w_shared_up"], f["w_shared_out"], q)
                chosen = chosen.reshape(n, S, -1)
            else:
                out = swiglu(h, f["w_gate"], f["w_up"], f["w_out"], q)
            return x + out.reshape(n, S, D), chosen
        return body

    def forward(params, tokens, start, count, q, routes=False):
        x = params["embed"][:d["vocab"]][tokens].astype(jnp.float32)
        x, _ = lax.scan(layer(False, q), x, params["dense_blocks"])
        x, chosen = lax.scan(layer(True, q), x, params["blocks"])
        if routes:
            return chosen
        x = rms(x[:, start:start + count], params["final_norm"]["scale"].astype(jnp.float32))
        head = params["lm_head"][:, :d["vocab"]]
        V = head.shape[1]
        width = -(-V // VOCAB_SLICES)
        head = jnp.pad(head, ((0, 0), (0, width * VOCAB_SLICES - V)))
        slices = head.reshape(head.shape[0], VOCAB_SLICES, width).transpose(1, 0, 2)
        out = lax.map(lambda w: mm(x, w.astype(jnp.float32), q), slices)   # (slices, n, c, w)
        return jnp.moveaxis(out, 0, 2).reshape(x.shape[0], x.shape[1], -1)[..., :V]

    return forward
