"""flash_roofline: the flash attention kernel's share of its roofline over
the ``flash_attention`` operations in the traced window.

Numerator: their causal FLOPs, from each operation's result shape
``(b, h, s, v)`` and the configuration's q.k width (``qk_nope_head_dim +
qk_rope_head_dim``, else ``hidden_size / num_attention_heads``):
``b h s(s+1)/2 (qk + v) 2`` (``bench/flops_mla.py``). Denominator: their
device time times the least of the bf16 peak and the HBM bandwidth times
the kernel's FLOPs per byte (q, k and v read and the output written once,
in the result's type)."""

import re

from bench.flops_mla import flash_bytes, flash_flops

OP = re.compile(r"%?flash_attention[\w.\-]* = \(?(\w+)\[(\d+),(\d+),(\d+),(\d+)\]")
ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}


def read(run):
    tr, cfg, peak = run.trace, run.config, run.peak
    if tr is None or peak is None:
        return None
    qk = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] if "qk_rope_head_dim" in cfg
          else cfg["hidden_size"] // cfg["num_attention_heads"])
    lo, hi = tr.window
    done = bound = 0.0
    for dev in tr.ops:
        for name, s, e in dev:
            m = OP.match(name)
            if not m or s < lo or e > hi:
                continue
            b, h, seq, v = (int(g) for g in m.groups()[1:])
            ops = flash_flops(b, h, seq, qk, v)
            intensity = ops / flash_bytes(b, h, seq, qk, v, ITEMSIZE.get(m.group(1), 2))
            done += ops
            bound += (e - s) / 1e9 * min(peak["bf16_flops_per_s"],
                                         peak["hbm_bytes_per_s"] * intensity)
    return 100.0 * done / bound if bound else None
