"""The DeepSeek-V2 cell's yardstick on the CPU: its FLOP counts from
shapes (``bench/flops_mla.py``) and its two trace readers,
``prefill_ms`` and ``flash_roofline``, on hand-built traces."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import flops_mla, harness, peaks  # noqa: E402
from bench.trace import Trace  # noqa: E402

MS = 1_000_000  # ns
CONFIG = harness.data("configs", "deepseek-v2-ep8")


def _run(tr, config=CONFIG, peak="TPU v5 lite"):
    return harness.Run(cell={}, config=config, traffic={}, units=[], spans=[],
                       window=(0.0, 1.0), traced=None, setup_s=1.0, trace=tr,
                       peak=peaks.lookup(peak) if peak else None)


def _flash(i, shape, start_ms, end_ms):
    dims = ",".join(map(str, shape))
    return (f"%flash_attention.{i} = bf16[{dims}]{{3,2,1,0:T(8,128)(2,1)}} custom-call(%a, %b, %c)",
            start_ms * MS, end_ms * MS)


def _trace():
    ops = [[_flash(6, (8, 128, 1024, 128), 10, 20), ("%fusion.3 = bf16[8,1024]{1,0} fusion(%x)",
                                                   20 * MS, 30 * MS),
            _flash(6, (8, 128, 1024, 128), 30, 50), _flash(7, (8, 128, 1024, 128), 95, 110)]]
    modules = [[("jit_prefill(12)", 5 * MS, 60 * MS), ("jit_decode_step(3)", 60 * MS, 70 * MS),
                ("jit_prefill(12)", 70 * MS, 90 * MS), ("jit_prefill(12)", 95 * MS, 120 * MS)]]
    return Trace(window=(0, 100 * MS), ops=ops, modules=modules,
                 spans=[("bench.window", 0, 100 * MS)])


def test_prefill_ms_reads_the_prefill_executions_in_the_window():
    reader = harness.reader("prefill_ms.serve")
    assert reader(_run(_trace())) == pytest.approx((55 + 20) / 2)
    assert reader(_run(None)) is None
    assert reader(_run(Trace(window=(0, 1), ops=[], modules=[], spans=[]))) is None


def test_flash_roofline_from_result_shapes_and_the_config_width():
    reader = harness.reader("flash_roofline.serve")
    peak = peaks.lookup("TPU v5 lite")
    ops = 2 * flops_mla.flash_flops(8, 128, 1024, 192, 128)      # the two ops inside
    intensity = flops_mla.flash_flops(1, 1, 1024, 192, 128) / \
        flops_mla.flash_bytes(1, 1, 1024, 192, 128)
    assert intensity == pytest.approx(1025 / 4)
    bound = min(peak["bf16_flops_per_s"], peak["hbm_bytes_per_s"] * intensity)
    assert reader(_run(_trace())) == pytest.approx(100 * ops / (0.030 * bound))
    # a config without MLA widths: q.k over hidden / heads
    qwen = harness.data("configs", "qwen2.5-3b")
    ops = 2 * flops_mla.flash_flops(8, 128, 1024, 128, 128)
    intensity = flops_mla.flash_flops(1, 1, 1024, 128, 128) / \
        flops_mla.flash_bytes(1, 1, 1024, 128, 128)
    bound = min(peak["bf16_flops_per_s"], peak["hbm_bytes_per_s"] * intensity)
    assert reader(_run(_trace(), config=qwen)) == pytest.approx(100 * ops / (0.030 * bound))


def test_flash_roofline_reads_nothing_without_a_trace_a_peak_or_a_kernel():
    reader = harness.reader("flash_roofline.serve")
    assert reader(_run(None)) is None
    assert reader(_run(_trace(), peak=None)) is None
    no_flash = Trace(window=(0, 100 * MS), ops=[[("%fusion.1 = f32[8]{0} fusion()", 0, MS)]],
                     modules=[], spans=[])
    assert reader(_run(no_flash)) is None


def test_flash_counts():
    assert flops_mla.flash_flops(2, 3, 4, 192, 128) == 2 * 2 * 3 * (4 * 5 / 2) * 320
    assert flops_mla.flash_bytes(2, 3, 4, 192, 128) == 2 * 3 * 4 * (2 * 192 + 2 * 128) * 2


def test_mla_moe_flops_against_the_weights_and_the_shapes():
    c = CONFIG
    # MLA of DeepSeek-V2: 149.2 M weights a layer
    assert flops_mla.attention_params(c) == pytest.approx(149.2e6, rel=1e-3)
    f = flops_mla.ffn_params(c)
    assert f["dense"] == 3 * 5120 * 12288
    # the router, the 2 shared experts and 6 x 20 / 160 routed experts a token
    assert f["moe"] == 5120 * 160 + 3 * 5120 * 1536 * (2 + 0.75)
    per = flops_mla.position_params(c)
    assert per == 6 * flops_mla.attention_params(c) + f["dense"] + 5 * f["moe"]
    head = 2 * 5120 * 102400
    assert flops_mla.head_flops(c) == head
    # one position: the layers, itself attended, the head
    attn1 = 2 * 6 * 128 * (128 + 64 + 128)
    assert flops_mla.prefill_flops(c, 1) == pytest.approx(2 * per + attn1 + head)
    attn = flops_mla.prefill_flops(c, 1024) - 2 * per * 1024 - head
    assert attn == pytest.approx(2 * 6 * 128 * 320 * 1024 * 1025 / 2)
    # absorbed decode: 512 + 64 for the score and 512 for the value per cached position
    step = flops_mla.decode_flops(c, 1000) - 2 * per - head
    assert step == pytest.approx(2 * 6 * 128 * (2 * 512 + 64) * 1000)
    call = flops_mla.serve_call_flops(c, 3, 5, 4)
    assert call == pytest.approx(3 * (flops_mla.prefill_flops(c, 5) + sum(
        flops_mla.decode_flops(c, ctx) for ctx in (6, 7, 8))))
