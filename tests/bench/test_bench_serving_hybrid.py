"""The Granite-4.0-H serving cell driven through the harness on the CPU with
a model of the same structure at tiny widths (a Mamba-2 block with a conv
bias and a NoPE attention block, an MoE of 4 experts with 2 held and a
shared MLP in each, the embedding, residual and logit multipliers), past
run.py's look for a chip: a whole run comes out correct; with the timed
path broken underneath (state dropped or left behind, rope applied), it
comes out not correct; and the control reads far above the program and
fails the cell's limit. In float32, so the program's gaps are those of
the order of operations alone. Logits divided by 2/3: at these widths and
2 layers that makes the control's widest gap read what it reads at the
cell's published widths and 10 layers on the chip (8.6e-3 here, 8.4e-3 to
9.6e-3 there), so that the cell's limit, set between the program's and
the control's readings, means here what it means there. Then the cell's
yardstick: ``bench/flops_hybrid.py`` and ``ssd_roofline`` on hand-worked
shapes."""

import dataclasses
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import calibrate, flops_hybrid, harness, peaks  # noqa: E402
from bench.paths import serving_hybrid  # noqa: E402
from bench.trace import Trace  # noqa: E402

CELL = "granite-4.0-h-small-longdoc-b32"
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, intermediate_size=16,
            shared_intermediate_size=32, num_hidden_layers=2, layer_types=["mamba", "attention"],
            num_local_experts=2, first_local_expert=1, num_experts_per_tok=2,
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
            vocab_size=500, torch_dtype="float32", attention_multiplier=0.25, logits_scaling=2 / 3,
            published=dict(num_hidden_layers=40, num_local_experts=4,
                           layer_types=["mamba", "attention"] * 20))
CONFIG = dict(harness.data("configs", "granite-4.0-h-small-ep8"), **TINY)
# prompts of 20, not a multiple of the chunk of 8
TRAFFIC = dict(harness.data("traffic", "offline-p8192-n256-b32"), requests_per_call=4,
               max_batch=4, prompt_len=20, new_tokens=6, pool=2, sample=64, trace_s=0.01)


def _run(trace=False, seconds=0.3, seed=2**31 + 77, config=CONFIG, traffic=TRAFFIC,
         control=False):
    return harness.run_cell(CELL, seed, seconds, trace, t_start=time.perf_counter(),
                            config=config, traffic=traffic, cache=False, control=control)


@pytest.mark.parametrize("trace", [False, True])
def test_a_run_is_correct(trace):
    result, checks = _run(trace)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] % TRAFFIC["requests_per_call"] == 0
    assert [c[0] for c in checks] == ["logit_gap"] and checks[0][1] < checks[0][2]
    spec = harness.load_spec()
    want = {m["name"] for m in harness.metrics_for(spec, CELL, trace)}
    device_only = {"idle_share.serve", "decode_step_ms.serve", "mfu.serve",
                   "prefill_ms.serve", "flash_roofline.serve", "ssd_roofline.serve"}
    assert want - device_only == set(result["metrics"])
    if not trace:
        assert result["metrics"]["tokens_per_s"]["value"] > 0
    else:
        assert "ssd_roofline.serve" in want


from repro.models.model import Model  # noqa: E402

DECODE, PREFILL, MODEL_CONFIG = Model.decode_step, Model.prefill, serving_hybrid.model_config


def _state_dropped(self, params, tokens, cache_len):
    logits, cache = PREFILL(self, params, tokens, cache_len)
    return logits, {n: a * 0 if n.endswith("_ssm") else a for n, a in cache.items()}


def _state_unchanged(self, params, token, cache, **kw):
    logits, new = DECODE(self, params, token, cache, **kw)
    return logits, dict(new, **{n: a for n, a in cache.items() if n.endswith("_ssm")})


def _rope_applied(config):
    return dataclasses.replace(MODEL_CONFIG(config), rope_theta=10000.0)


FAULTS = {"state_dropped": (Model, "prefill", _state_dropped),
          "state_unchanged": (Model, "decode_step", _state_unchanged),
          "rope_applied": (serving_hybrid, "model_config", _rope_applied)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_path_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(*FAULTS[fault])
    result, checks = _run()
    assert not result["correct"], checks


def test_the_control_reads_far_above_the_program():
    cell = harness.cell_of(harness.load_spec(), CELL)
    rows = calibrate.readings(cell, CONFIG, TRAFFIC, [3, 4], 0.2)
    for row in rows:
        assert row["control.logit_gap"] > 3 * row["logit_gap"]
        assert row["control.logit_gap"] > 0


def test_the_control_is_not_correct():
    """The control in the program's place fails the cell's own limit."""
    result, checks = _run(seconds=0.01, seed=4, control=True)
    assert not result["correct"], checks


# --- the yardstick ------------------------------------------------------------

MS = 1_000_000  # ns
FULL = harness.data("configs", "granite-4.0-h-small-ep8")


def test_ssd_counts_by_hand():
    # one sequence, one head, two chunks of Q = 4, P = 2, N = 3:
    # Q(Q+1)(N+P) + 4QPN = 4*5*5 + 4*4*2*3 = 196 a chunk
    assert flops_hybrid.ssd_flops(1, 1, 8, 4, 2, 3) == 2 * 196
    # x and y 8 x 2 in bf16 and dt 8 in f32 a head, B and C 8 x 3 in bf16
    # per group, the final state 2 x 3 in f32
    assert flops_hybrid.ssd_bytes(1, 1, 1, 8, 2, 3) == 8 * (2 * 2 * 2 + 4) + 4 * 6 + 2 * 8 * 3 * 2
    # Granite's prefill block: 128 heads over 8192 positions in 32 chunks of 256
    assert flops_hybrid.ssd_flops(1, 128, 8192, 256, 64, 128) == \
        128 * 32 * (256 * 257 * 192 + 4 * 256 * 64 * 128)


def test_flops_from_the_published_widths():
    assert flops_hybrid.mamba_params(FULL) == \
        4096 * (2 * 8192 + 2 * 128 + 128) + 4 * 8448 + 8192 * 4096
    assert flops_hybrid.attention_params(FULL) == 4096 * 128 * (32 + 16) + 32 * 128 * 4096
    # router, 10 x 9 / 72 = 1.25 routed experts a token, the shared MLP of 1536
    assert flops_hybrid.ffn_params(FULL) == 4096 * 72 + 3 * 4096 * 768 * 1.25 + 3 * 4096 * 1536
    per_token = flops_hybrid.prefill_flops(FULL, 8192) / 8192
    assert per_token == pytest.approx(2.706e9, rel=1e-3)
    call = flops_hybrid.serve_call_flops(FULL, 32, 8192, 256)
    assert call == pytest.approx(32 * (flops_hybrid.prefill_flops(FULL, 8192) + sum(
        flops_hybrid.decode_flops(FULL, 8192 + t + 1) for t in range(255))))


def _run_of(tr, config=FULL, peak="TPU v5 lite"):
    return harness.Run(cell={}, config=config, traffic={}, units=[], spans=[],
                       window=(0.0, 1.0), traced=None, setup_s=1.0, trace=tr,
                       peak=peaks.lookup(peak) if peak else None)


def _ssd(i, start_ms, end_ms, shape=(1, 128, 8192, 64)):
    dims = ",".join(map(str, shape))
    return (f"%ssd_scan.{i} = (bf16[{dims}]{{3,2,1,0:T(8,128)(2,1)}}, "
            f"f32[1,128,64,128]{{3,2,1,0:T(8,128)}}) custom-call(%a, %b, %c, %d, %e)",
            start_ms * MS, end_ms * MS)


def test_ssd_roofline_from_result_shapes_and_the_config():
    reader = harness.reader("ssd_roofline.serve")
    peak = peaks.lookup("TPU v5 lite")
    ops = [[_ssd(1, 10, 20), ("%fusion.3 = bf16[8,1024]{1,0} fusion(%x)", 20 * MS, 30 * MS),
            _ssd(2, 30, 50), _ssd(3, 95, 110)]]
    tr = Trace(window=(0, 100 * MS), ops=ops, modules=[], spans=[])
    flops = flops_hybrid.ssd_flops(1, 128, 8192, 256, 64, 128)
    intensity = flops / flops_hybrid.ssd_bytes(1, 128, 1, 8192, 64, 128)
    assert intensity == pytest.approx(306.4, rel=1e-3)           # bound by the bf16 peak
    bound = min(peak["bf16_flops_per_s"], peak["hbm_bytes_per_s"] * intensity)
    assert reader(_run_of(tr)) == pytest.approx(100 * 2 * flops / (0.030 * bound))
    assert reader(_run_of(None)) is None
    assert reader(_run_of(tr, peak=None)) is None
    assert reader(_run_of(tr, config=harness.data("configs", "qwen2.5-3b"))) is None
    no_ssd = Trace(window=(0, 100 * MS), ops=[[ops[0][1]]], modules=[], spans=[])
    assert reader(_run_of(no_ssd)) is None
