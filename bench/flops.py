"""Model FLOPs from shapes: two per multiply-add of the model's matrix
products and convolutions. Elementwise work (norms, activations, residual
adds, softmax) and the embedding gather are not counted.
"""

from __future__ import annotations


def mobilenetv2_macs(config: dict) -> int:
    """Multiply-adds of one image through MobileNetV2's convolutions and
    classifier, from the published inverted-residual schedule."""
    size = config["image_size"]
    h = -(-size // 2)                                   # stem: 3x3, stride 2
    cin = config["stem_channels"]
    macs = h * h * 9 * config["in_channels"] * cin
    for t, c, n, s in config["inverted_residual_setting"]:
        for i in range(n):
            stride = s if i == 0 else 1
            hidden = cin * t
            if t != 1:
                macs += h * h * cin * hidden            # 1x1 expand
            h = -(-h // stride)
            macs += h * h * 9 * hidden                  # 3x3 depthwise
            macs += h * h * hidden * c                  # 1x1 project
            cin = c
    macs += h * h * cin * config["last_channels"]       # 1x1 to 1280
    macs += config["last_channels"] * config["num_classes"]
    return macs


def mobilenetv2_flops_per_image(config: dict) -> float:
    return 2.0 * mobilenetv2_macs(config)


def qwen2_matmul_params(config: dict) -> int:
    """Weights every position multiplies by: the blocks' projections and
    the (tied) output head."""
    d, ff = config["hidden_size"], config["intermediate_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // heads
    per_layer = d * heads * hd + 2 * d * kv * hd + heads * hd * d + 3 * d * ff
    return config["num_hidden_layers"] * per_layer + d * config["vocab_size"]


def qwen2_flops_per_position(config: dict, context: int) -> float:
    """One position through the model, attending to ``context`` positions
    (itself included): 2 per weight, plus q.k and p.v over the context."""
    heads = config["num_attention_heads"]
    hd = config["hidden_size"] // heads
    attn = 4 * config["num_hidden_layers"] * heads * hd * context
    return 2.0 * qwen2_matmul_params(config) + attn


def qwen2_serve_call_flops(config: dict, requests: int, prompt_len: int,
                           new_tokens: int) -> float:
    """One greedy serve call that steps every request through its prompt
    and generated tokens one position at a time (P + N - 1 steps)."""
    steps = prompt_len + new_tokens - 1
    return requests * sum(qwen2_flops_per_position(config, t + 1) for t in range(steps))
