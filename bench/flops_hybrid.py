"""Model FLOPs of a Mamba-2 / attention hybrid MoE decoder (Granite-4.0-H)
served by prefill and decode steps, and the operations and bytes of the
SSD scan kernel, from shapes: two per multiply-add of the matrix products
and convolutions. Elementwise work (norms, activations, softmax, gates,
routing's sort) and the embedding gather are not counted.

As computed: a Mamba-2 layer's projections and depthwise conv for every
position; its scan in the prefill as the chunked kernel does it
(``ssd_flops``: the causal quadratic term inside each chunk of
``mamba_chunk_size``, the carried state's output and its update), in a
decode step as the recurrence (state update and readout, ``4 P N`` a
head). Attention causal over the prompt in the prefill (``bench/
flops_mla.py``'s ``flash_flops``), over the context in a decode step.
Routed experts count at the share held here (``num_experts_per_tok *
num_local_experts / published num_local_experts`` experts a token), the
shared MLP and the router in full; the tied head only where logits are
used: at the last prompt position and at each decode step.
"""

from __future__ import annotations

from bench.flops_mla import flash_flops


def _mamba(config: dict):
    """(heads, head width P, groups, state N, inner width, conv taps)."""
    h, p = config["mamba_n_heads"], config["mamba_d_head"]
    return (h, p, config["mamba_n_groups"], config["mamba_d_state"], h * p,
            config["mamba_d_conv"])


def mamba_params(config: dict) -> int:
    """Weights of one Mamba-2 layer that every position multiplies by: the
    in-projection (z, x, B, C, dt), the depthwise conv and the
    out-projection."""
    d = config["hidden_size"]
    h, _, g, n, di, k = _mamba(config)
    return d * (2 * di + 2 * g * n + h) + k * (di + 2 * g * n) + di * d


def attention_params(config: dict) -> int:
    d, heads, kv = config["hidden_size"], config["num_attention_heads"], \
        config["num_key_value_heads"]
    hd = d // heads
    return d * hd * (heads + 2 * kv) + heads * hd * d


def ffn_params(config: dict) -> float:
    """Weights a position multiplies by, on average over tokens, in one MoE
    layer with the held share of routed experts and the shared MLP."""
    d, f = config["hidden_size"], config["intermediate_size"]
    experts = config["published"]["num_local_experts"]
    routed = config["num_experts_per_tok"] * config["num_local_experts"] / experts
    return d * experts + 3 * d * f * routed + 3 * d * config["shared_intermediate_size"]


def _counts(config: dict):
    kinds = config["layer_types"]
    return kinds.count("mamba"), kinds.count("attention")


def position_params(config: dict) -> float:
    """Weights one position multiplies by in the layers (the head apart)."""
    mamba, attn = _counts(config)
    return (mamba * mamba_params(config) + attn * attention_params(config)
            + (mamba + attn) * ffn_params(config))


def head_flops(config: dict) -> float:
    return 2.0 * config["hidden_size"] * config["vocab_size"]


def ssd_flops(batch: int, heads: int, seq: int, chunk: int, p: int, n: int) -> float:
    """The chunked SSD scan over ``seq`` positions (a multiple of
    ``chunk``): per (sequence, head, chunk) of Q positions, C.B^T and the
    gated x over the Q(Q+1)/2 causal pairs, the carried state's output and
    its update: Q(Q+1)(N+P) + 4QPN."""
    q = chunk
    return float(batch * heads * (seq // chunk) * (q * (q + 1) * (n + p) + 4 * q * p * n))


def ssd_bytes(batch: int, heads: int, groups: int, seq: int, p: int, n: int,
              itemsize: int = 2) -> float:
    """HBM bytes the SSD kernel needs at least: x read and y written per
    head in the activations' type, dt per head in float32, B and C per
    group read once, the final state written in float32."""
    per_head = seq * (2 * p * itemsize + 4) + 4 * p * n
    return float(batch * (heads * per_head + 2 * groups * seq * n * itemsize))


def prefill_flops(config: dict, prompt_len: int) -> float:
    """One sequence's prefill: every position through the layers, the
    chunked scans, causal attention over the prompt, the head at the last
    position."""
    mamba, attn = _counts(config)
    h, p, _, n, _, _ = _mamba(config)
    chunk = min(config["mamba_chunk_size"], prompt_len)
    padded = -(-prompt_len // chunk) * chunk
    hd = config["hidden_size"] // config["num_attention_heads"]
    return (2.0 * position_params(config) * prompt_len
            + mamba * ssd_flops(1, h, padded, chunk, p, n)
            + attn * flash_flops(1, config["num_attention_heads"], prompt_len, hd, hd)
            + head_flops(config))


def decode_flops(config: dict, context: int) -> float:
    """One decode step of one sequence attending to ``context`` cached
    positions (itself included), with the head."""
    mamba, attn = _counts(config)
    h, p, _, n, _, _ = _mamba(config)
    heads = config["num_attention_heads"]
    hd = config["hidden_size"] // heads
    return (2.0 * position_params(config) + mamba * 4.0 * h * p * n
            + attn * 4.0 * heads * hd * context + head_flops(config))


def serve_call_flops(config: dict, requests: int, prompt_len: int, new_tokens: int) -> float:
    """One greedy serve call: a prefill of each prompt, then N - 1 decode
    steps (the first token comes from the prefill's logits)."""
    steps = sum(decode_flops(config, prompt_len + t + 1) for t in range(new_tokens - 1))
    return requests * (prefill_flops(config, prompt_len) + steps)
