"""Plain Qwen2 decoder in ``jax.numpy``: the reference the served tokens
are compared with.

Written from the Qwen2 architecture (``Qwen2ForCausalLM``): token
embedding; per layer RMSNorm, grouped-query attention whose q, k and v
projections carry a bias, rotary embedding on the two halves of each
head (theta from the config), causal softmax, output projection and a
residual add, then RMSNorm and a SwiGLU MLP (``down(silu(gate(x)) *
up(x))``) and a residual add; a final RMSNorm and the output head tied to
the embedding. It imports nothing of the program. Float32 at highest
precision over the whole sequence, one layer at a time.

The weights are read by the names of the served parameter tree:
``embed`` (rows past ``vocab_size`` are padding and ignored),
``final_norm/scale`` and, stacked over layers, ``blocks/{ln1,ln2}/scale``,
``blocks/attn/{w_q,w_k,w_v,w_o,b_q,b_k,b_v}`` (``x @ w`` layout) and
``blocks/ffn/{w_gate,w_up,w_out}``.

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


def _round(x, per_row: bool, quant):
    """``x`` rounded to e4m3 after scaling; ``reduce_precision`` and not a
    pair of converts, which XLA may drop."""
    if quant is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True) if per_row else jnp.max(jnp.abs(x))
    s = FP8_MAX / jnp.maximum(amax, 1e-30)
    return lax.reduce_precision(x * s, exponent_bits=4, mantissa_bits=3) / s


def _dims(config: dict):
    d, heads = config["hidden_size"], config["num_attention_heads"]
    return (d, heads, config["num_key_value_heads"], d // heads, config["vocab_size"],
            float(config["rms_norm_eps"]), float(config["rope_theta"]))


def logits(config: dict, params, tokens, start: int, count: int, quant=None):
    """Reference logits ``(n, count, vocab)`` at positions ``start`` to
    ``start + count - 1`` of ``tokens`` ``(n, S)``."""
    return _logits(_dims(config), start, count, quant)(params, jnp.asarray(tokens))


def gaps(config: dict, params, tokens, served, prompt_len: int, quant=None):
    """Per generated position, how far the served token's logit lies below
    the reference's best: ``(n, N)`` float32, 0 where the served token is
    the reference's argmax.

    ``tokens``: ``(n, P + N - 1)`` prompt and served tokens but the last;
    ``served``: ``(n, N)``. With ``quant``, also returns the same gap of
    the token that the lower-precision forward ranks first.
    """
    fn = _gaps(_dims(config), prompt_len, served.shape[1], quant)
    return fn(params, jnp.asarray(tokens), jnp.asarray(served))


@functools.lru_cache(maxsize=None)
def _logits(dims, start: int, count: int, quant):
    forward = _forward(dims)
    return jax.jit(lambda params, tokens: forward(params, tokens, start, count, quant))


@functools.lru_cache(maxsize=None)
def _gaps(dims, prompt_len: int, new: int, quant):
    forward = _forward(dims)

    def run(params, tokens, served):
        logits = forward(params, tokens, prompt_len - 1, new, None)
        best = logits.max(-1)
        gap = best - jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
        if quant is None:
            return gap
        top = forward(params, tokens, prompt_len - 1, new, quant).argmax(-1)
        return gap, best - jnp.take_along_axis(logits, top[..., None], -1)[..., 0]

    return jax.jit(run)


def _forward(dims):
    d, heads, kv, hd, vocab, eps, theta = dims

    def mm(x, w, q):
        return jnp.matmul(_round(x, True, q), _round(w, False, q), precision=HIGHEST)

    def rms(x, scale):
        return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale

    def rope(x, pos):                      # x: (n, S, h, hd)
        inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        ang = pos[:, None].astype(jnp.float32) * inv[None, :]
        cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
        sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
        half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
        return x * cos + half * sin

    def forward(params, tokens, start, count, q):
        n, S = tokens.shape
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        emb = f32(params["embed"][:vocab])
        pos = jnp.arange(S)
        causal = pos[:, None] >= pos[None, :]

        def layer(x, p):
            p = jax.tree.map(f32, p)
            a = p["attn"]
            h = rms(x, p["ln1"]["scale"])
            qh = (mm(h, a["w_q"], q) + a["b_q"]).reshape(n, S, heads, hd)
            kh = (mm(h, a["w_k"], q) + a["b_k"]).reshape(n, S, kv, hd)
            vh = (mm(h, a["w_v"], q) + a["b_v"]).reshape(n, S, kv, hd)
            qh, kh = rope(qh, pos), rope(kh, pos)
            kh = jnp.repeat(kh, heads // kv, axis=2)
            vh = jnp.repeat(vh, heads // kv, axis=2)
            s = jnp.einsum("nqhd,nkhd->nhqk", _round(qh, True, q), _round(kh, True, q),
                           precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
            w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            o = jnp.einsum("nhqk,nkhd->nqhd", _round(w, True, q), _round(vh, False, q),
                           precision=HIGHEST)
            x = x + mm(o.reshape(n, S, heads * hd), a["w_o"], q)
            h = rms(x, p["ln2"]["scale"])
            f = p["ffn"]
            return x + mm(jax.nn.silu(mm(h, f["w_gate"], q)) * mm(h, f["w_up"], q),
                          f["w_out"], q), None

        x, _ = lax.scan(layer, emb[tokens], params["blocks"])
        x = rms(x[:, start:start + count], f32(params["final_norm"]["scale"]))
        return mm(x, emb.T, q)

    return forward
