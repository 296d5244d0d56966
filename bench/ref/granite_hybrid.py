"""Plain Granite-4.0-H decoder (``granitemoehybrid``) in ``jax.numpy``: the
reference the served tokens are compared with.

Written from the model's config.json and the Granite-4.0-H / Mamba-2
equations (arXiv 2405.21060): token embedding times
``embedding_multiplier``; per layer RMSNorm, the layer's mixer (Mamba-2 or
attention, by ``layer_types``), the residual add of the mixer's output
times ``residual_multiplier``, RMSNorm, the MoE and shared MLP, and the
same residual add; a final RMSNorm and the tied head, logits divided by
``logits_scaling``. It imports nothing of the program. Float32 at highest
precision.

- Mamba-2: z, x, B, C and dt from the input; a causal depthwise conv of
  ``mamba_d_conv`` taps with a bias over (x, B, C), then SiLU;
  dt = softplus(dt + dt_bias), A = -exp(a_log). The recurrence, one
  position at a time from a zero state: h_t = exp(dt_t A) h_{t-1} +
  dt_t x_t B_t^T, y_t = C_t h_t + D x_t (heads share their group's B and
  C); then y times SiLU(z), an RMSNorm over the full ``mamba_expand *
  hidden_size`` width, and the out-projection. The chunked duality the
  kernel uses does not appear.
- Attention: GQA without any position embedding (``nope``), causal,
  softmax scale ``attention_multiplier``; one sequence and one block of
  query rows at a time.
- MoE: the top ``num_experts_per_tok`` of the ``published`` count of
  router logits, softmax over the chosen ones. The routed part sums, over
  the experts held here (``num_local_experts`` from
  ``first_local_expert``), each expert's SwiGLU output times its weight:
  every held expert is computed for every token and weighted by zero where
  not chosen. The shared MLP (``shared_intermediate_size``) is added.

Departures from the published model, shared with the program: the
routed experts held elsewhere add nothing (one chip's share of an
expert-parallel deployment), and the depth is the configuration's.

The weights are read by the names of the served parameter tree: ``embed``
(tied head), ``final_norm/scale``; stacked over super-blocks,
``super/b{j}_{mamba,attn}/...`` with ``{ln1,ln2}/scale``,
``mixer/{w_z,w_x,w_b,w_c,w_dt,conv_x,conv_b,conv_c,conv_bias,dt_bias,
a_log,d_skip,norm,w_out}`` (``x @ w`` layout), ``attn/{w_q,w_k,w_v,w_o}``
and ``ffn/{router,w_in,w_out,w_shared_gate,w_shared_up,w_shared_out}``
(``w_in`` holds each expert's up then gate projection). Layer ``i`` is
block ``i % period`` of super-block ``i // period``. Each layer's weights
are taken up to float32 one layer at a time.

With ``quant="fp8"`` every matrix product takes its operands rounded to
8-bit floats with 4 exponent and 3 mantissa bits (e4m3; weights scaled
per tensor, activations per row, to the format's largest finite value):
the control, which ranks tokens in the nearest precision below the served
bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
FP8_MAX = 240.0      # the largest finite e4m3 value of lax.reduce_precision
Q_ROWS = 128         # query rows of one attention block
VOCAB_SLICES = 8     # slices of the head
KINDS = {"mamba": "mamba", "attention": "attn"}     # layer_types -> block names


def _round(x, per_row: bool, quant):
    """``x`` rounded to e4m3 after scaling; ``reduce_precision`` and not a
    pair of converts, which XLA may drop."""
    if quant is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True) if per_row else jnp.max(jnp.abs(x))
    s = FP8_MAX / jnp.maximum(amax, 1e-30)
    return lax.reduce_precision(x * s, exponent_bits=4, mantissa_bits=3) / s


def _dims(config: dict):
    heads = config["num_attention_heads"]
    return dict(
        kinds=tuple(KINDS[t] for t in config["layer_types"]),
        heads=heads, kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // heads, scale=float(config["attention_multiplier"]),
        emb=float(config["embedding_multiplier"]), res=float(config["residual_multiplier"]),
        logit_div=float(config["logits_scaling"]), eps=float(config["rms_norm_eps"]),
        vocab=config["vocab_size"], top_k=config["num_experts_per_tok"],
        first=config["first_local_expert"], held=config["num_local_experts"],
        ssm_heads=config["mamba_n_heads"], ssm_p=config["mamba_d_head"],
        groups=config["mamba_n_groups"], state=config["mamba_d_state"])


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


def route(logits, top_k: int, experts: int):
    """Routing weights ``(T, experts)``: softmax over each token's top_k
    logits, zero for the experts not chosen."""
    top, chosen = lax.top_k(logits, top_k)
    w = jax.nn.softmax(top, axis=-1)
    onehot = chosen[:, :, None] == jnp.arange(experts)[None, None, :]
    return (onehot * w[:, :, None]).sum(1)


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
    H, Hkv, hd = d["heads"], d["kv_heads"], d["head_dim"]
    SH, SP, G, N = d["ssm_heads"], d["ssm_p"], d["groups"], d["state"]

    def mm(x, w, q):
        return jnp.matmul(_round(x, True, q), _round(w, False, q), precision=HIGHEST)

    def rms(x, scale):
        return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + d["eps"]) * scale

    def swiglu(h, w_gate, w_up, w_down, q):
        return mm(jax.nn.silu(mm(h, w_gate, q)) * mm(h, w_up, q), w_down, q)

    def mamba(m, h, q):                    # one sequence: h (S, D)
        S = h.shape[0]
        z = mm(h, m["w_z"], q)
        xbc = jnp.concatenate([mm(h, m["w_x"], q), mm(h, m["w_b"], q), mm(h, m["w_c"], q)], -1)
        w = jnp.concatenate([m["conv_x"], m["conv_b"], m["conv_c"]], -1)      # (K, C)
        K = w.shape[0]
        padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), xbc.dtype), xbc])
        conv = sum(padded[i:i + S] * w[i] for i in range(K)) + m["conv_bias"]
        xbc = jax.nn.silu(conv)
        di = SH * SP
        x = xbc[:, :di].reshape(S, SH, SP)
        b = xbc[:, di:di + G * N].reshape(S, G, N)
        c = xbc[:, di + G * N:].reshape(S, G, N)
        dt = jax.nn.softplus(mm(h, m["w_dt"], q) + m["dt_bias"])                # (S, H)
        a = -jnp.exp(m["a_log"])

        def step(state, t):                # state (H, P, N), elementwise f32
            xt, bt, ct, dtt = t
            bt, ct = (jnp.repeat(u, SH // G, axis=0) for u in (bt, ct))      # (H, N)
            state = (jnp.exp(dtt * a)[:, None, None] * state
                     + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
            return state, (state * ct[:, None, :]).sum(-1)

        _, y = lax.scan(step, jnp.zeros((SH, SP, N), jnp.float32), (x, b, c, dt))
        y = (y + m["d_skip"][None, :, None] * x).reshape(S, di)
        y = rms(y * jax.nn.silu(z), m["norm"])
        return mm(y, m["w_out"], q)

    def attention(a, h, q):                # one sequence: h (S, D), no position embedding
        S = h.shape[0]
        qh = mm(h, a["w_q"], q).reshape(S, H, hd)
        k = jnp.repeat(mm(h, a["w_k"], q).reshape(S, Hkv, hd), H // Hkv, axis=1)
        v = jnp.repeat(mm(h, a["w_v"], q).reshape(S, Hkv, hd), H // Hkv, axis=1)
        k, v = _round(k, True, q), _round(v, False, q)
        pos = jnp.arange(S)
        rows = -(-S // Q_ROWS) * Q_ROWS
        qp = jnp.pad(qh, ((0, rows - S), (0, 0), (0, 0))).reshape(rows // Q_ROWS, Q_ROWS, H, hd)

        def block(args):                   # Q_ROWS query rows
            i, qb = args
            s = jnp.einsum("qhd,khd->hqk", _round(qb, True, q), k, precision=HIGHEST) * d["scale"]
            causal = i * Q_ROWS + jnp.arange(Q_ROWS)[:, None] >= pos[None, :]
            w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khv->qhv", _round(w, True, q), v, precision=HIGHEST)

        o = lax.map(block, (jnp.arange(rows // Q_ROWS), qp)).reshape(rows, H * hd)[:S]
        return mm(o, a["w_o"], q)

    def moe(f, h, q):                      # h (T, D): the held experts' part and the shared MLP
        logits = mm(h, f["router"], q)
        weights = route(logits, d["top_k"], logits.shape[1])
        held = lax.dynamic_slice_in_dim(weights, d["first"], d["held"], axis=1)

        def expert(y, args):
            w_in, w_out, gate = args
            up, g = jnp.split(mm(h, w_in, q), 2, axis=-1)
            return y + gate[:, None] * mm(up * jax.nn.silu(g), w_out, q), None

        y, _ = lax.scan(expert, jnp.zeros_like(h), (f["w_in"], f["w_out"], held.T))
        return y + swiglu(h, f["w_shared_gate"], f["w_shared_up"], f["w_shared_out"], q)

    def layer(x, p, kind, q):              # x (n, S, D)
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        n, S, D = x.shape
        h = rms(x, p["ln1"]["scale"])
        if kind == "mamba":
            out = lax.map(lambda hs: mamba(p["mixer"], hs, q), h)
        else:
            out = lax.map(lambda hs: attention(p["attn"], hs, q), h)
        x = x + d["res"] * out
        h = rms(x, p["ln2"]["scale"]).reshape(n * S, D)
        return x + d["res"] * moe(p["ffn"], h, q).reshape(n, S, D)

    def forward(params, tokens, start, count, q):
        blocks = params["super"]
        period = len(blocks)
        assert len(d["kinds"]) % period == 0 and "tail0" not in params
        x = params["embed"][:d["vocab"]][tokens].astype(jnp.float32) * d["emb"]
        for i, kind in enumerate(d["kinds"]):
            p = jax.tree.map(lambda a: a[i // period], blocks[f"b{i % period}_{kind}"])
            x = layer(x, p, kind, q)
        x = rms(x[:, start:start + count], params["final_norm"]["scale"].astype(jnp.float32))
        head = params["embed"][:d["vocab"]].T
        V = head.shape[1]
        width = -(-V // VOCAB_SLICES)
        head = jnp.pad(head, ((0, 0), (0, width * VOCAB_SLICES - V)))
        slices = head.reshape(head.shape[0], VOCAB_SLICES, width).transpose(1, 0, 2)
        out = lax.map(lambda w: mm(x, w.astype(jnp.float32), q), slices)   # (slices, n, c, w)
        out = jnp.moveaxis(out, 0, 2).reshape(x.shape[0], x.shape[1], -1)[..., :V]
        return out / d["logit_div"]

    return forward
