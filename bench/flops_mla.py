"""Model FLOPs of a latent-attention MoE decoder (DeepSeek-V2) served by
prefill and decode steps, and the operations and bytes of the flash
attention kernel, from shapes: two per multiply-add of the matrix
products. Elementwise work (norms, activations, rope, softmax, routing's
sort) and the embedding gather are not counted.

As computed: the prefill runs MLA in its full form (k and v expanded per
head, causal attention over q.k of ``qk_nope + qk_rope`` and p.v of
``v_head_dim``); a decode step runs it absorbed (each cached position
costs ``kv_lora_rank + qk_rope`` for the score and ``kv_lora_rank`` for
the value, per head). Routed experts count at the share held here
(``num_experts_per_tok * n_routed_experts / published n_routed_experts``
experts a token), the shared experts and the router in full; the output
head only where logits are used: at the last prompt position and at each
decode step.
"""

from __future__ import annotations


def _widths(config: dict):
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, v = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    return d, heads, nope, rope, v, config["q_lora_rank"], config["kv_lora_rank"]


def attention_params(config: dict) -> int:
    """Weights of one MLA layer that every position multiplies by."""
    d, h, nope, rope, v, qr, kr = _widths(config)
    return d * qr + qr * h * (nope + rope) + d * (kr + rope) + kr * h * (nope + v) + h * v * d


def ffn_params(config: dict) -> dict:
    """Weights a position multiplies by in the dense layer and, on average
    over tokens, in one MoE layer with the held share of routed experts."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    routed = config["num_experts_per_tok"] * config["n_routed_experts"] \
        / config["published"]["n_routed_experts"]
    moe = (d * config["published"]["n_routed_experts"]
           + 3 * d * f * (config["n_shared_experts"] + routed))
    return {"dense": 3 * d * config["intermediate_size"], "moe": moe}


def position_params(config: dict) -> float:
    """Weights one position multiplies by in the layers (the head apart)."""
    dense = config["first_k_dense_replace"]
    moe = config["num_hidden_layers"] - dense
    f = ffn_params(config)
    return config["num_hidden_layers"] * attention_params(config) + dense * f["dense"] \
        + moe * f["moe"]


def head_flops(config: dict) -> float:
    return 2.0 * config["hidden_size"] * config["vocab_size"]


def prefill_flops(config: dict, prompt_len: int) -> float:
    """One sequence's prefill: every position through the layers, causal
    attention over the full prompt, the head at the last position."""
    _, h, nope, rope, v, _, _ = _widths(config)
    pairs = prompt_len * (prompt_len + 1) // 2
    attn = 2.0 * config["num_hidden_layers"] * h * (nope + rope + v) * pairs
    return 2.0 * position_params(config) * prompt_len + attn + head_flops(config)


def decode_flops(config: dict, context: int) -> float:
    """One decode step of one sequence attending to ``context`` cached
    positions (itself included), absorbed, with the head."""
    _, h, _, rope, _, _, kr = _widths(config)
    attn = 2.0 * config["num_hidden_layers"] * h * (2 * kr + rope) * context
    return 2.0 * position_params(config) + attn + head_flops(config)


def serve_call_flops(config: dict, requests: int, prompt_len: int, new_tokens: int) -> float:
    """One greedy serve call: a prefill of each prompt, then N - 1 decode
    steps (the first token comes from the prefill's logits)."""
    steps = sum(decode_flops(config, prompt_len + t + 1) for t in range(new_tokens - 1))
    return requests * (prefill_flops(config, prompt_len) + steps)


def flash_flops(batch: int, heads: int, seq: int, qk: int, v: int) -> float:
    """Causal self-attention in the flash kernel: q.k and p.v over the
    s(s+1)/2 pairs a causal mask keeps."""
    return 2.0 * batch * heads * seq * (seq + 1) / 2 * (qk + v)


def flash_bytes(batch: int, heads: int, seq: int, qk: int, v: int, itemsize: int = 2) -> float:
    """HBM bytes the flash kernel needs at least: q and k read, v read, the
    output written, once each."""
    return float(batch * heads * seq * (2 * qk + 2 * v) * itemsize)
