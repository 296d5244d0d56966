"""ssd_roofline: the Mamba-2 SSD scan kernel's share of its roofline over
the ``ssd_scan`` operations in the traced window.

Numerator: the work the chunked algorithm must do, from each operation's
first result ``(b, h, L, P)`` (y, heads before length) and the
configuration's state width, groups and chunk (``mamba_d_state``,
``mamba_n_groups``, ``mamba_chunk_size``): per (sequence, head, chunk) of
Q positions, Q(Q+1)(N+P) + 4QPN (``bench/flops_hybrid.py``). Denominator:
their device time times the least of the bf16 peak and the HBM bandwidth
times the kernel's FLOPs per byte (x, dt and y per head, B and C per
group, the final state)."""

import re

from bench.flops_hybrid import ssd_bytes, ssd_flops

OP = re.compile(r"%?ssd_scan[\w.\-]* = \(?(\w+)\[(\d+),(\d+),(\d+),(\d+)\]")
ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}


def read(run):
    tr, cfg, peak = run.trace, run.config, run.peak
    if tr is None or peak is None or "mamba_d_state" not in cfg:
        return None
    n, g, chunk = cfg["mamba_d_state"], cfg["mamba_n_groups"], cfg["mamba_chunk_size"]
    lo, hi = tr.window
    done = bound = 0.0
    for dev in tr.ops:
        for name, s, e in dev:
            m = OP.match(name)
            if not m or s < lo or e > hi:
                continue
            b, h, seq, p = (int(x) for x in m.groups()[1:])
            ops = ssd_flops(b, h, seq, min(chunk, seq), p, n)
            intensity = ops / ssd_bytes(b, h, g, seq, p, n, ITEMSIZE.get(m.group(1), 2))
            done += ops
            bound += (e - s) / 1e9 * min(peak["bf16_flops_per_s"],
                                         peak["hbm_bytes_per_s"] * intensity)
    return 100.0 * done / bound if bound else None
