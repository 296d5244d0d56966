"""The DeepSeek-V2 serving cell driven through the harness on the CPU with
a model of the same structure at tiny widths (MLA with q and kv LoRA, a
dense layer 0, group-limited routing over 4 groups, a held share of the
routed experts, 2 shared experts, YaRN rope), past run.py's look for a
chip: a whole run comes out correct; with the timed path broken
underneath, it comes out not correct; and the control reads far above the
program and fails the cell's limit. In float32: at these widths a token
routed to another expert than the reference's moves the logits by several
units (eight experts, weights scaled by 16), and bfloat16 rounding
reroutes some."""

import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import calibrate, harness  # noqa: E402

CELL = "deepseek-v2-conv-b128"
TINY = dict(hidden_size=64, intermediate_size=96, num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8, moe_intermediate_size=16, n_group=4,
            topk_group=2, num_experts_per_tok=3, n_routed_experts=4, first_routed_expert=2,
            vocab_size=500, torch_dtype="float32",
            published=dict(num_hidden_layers=60, n_routed_experts=8))
CONFIG = dict(harness.data("configs", "deepseek-v2-ep8"), **TINY)
TRAFFIC = dict(harness.data("traffic", "offline-p1024-n128-b128"), requests_per_call=4,
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
    assert [c[0] for c in checks] == ["logit_gap_mean"] and checks[0][1] < checks[0][2]
    spec = harness.load_spec()
    want = {m["name"] for m in harness.metrics_for(spec, CELL, trace)}
    device_only = {"idle_share.serve", "decode_step_ms.serve", "mfu.serve",
                   "prefill_ms.serve", "flash_roofline.serve"}
    assert want - device_only == set(result["metrics"])
    if not trace:
        assert result["metrics"]["tokens_per_s"]["value"] > 0


from repro.models.model import Model  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402

DECODE, PREFILL, GENERATE = Model.decode_step, Model.prefill, ServingEngine._generate_group


def _request_altered(self, group):
    """Every token of a group's first request off by one: the cell is held
    to the mean gap over its sampled positions, which one token alone moves
    too little to read."""
    out = GENERATE(self, group)
    out[0] = (out[0] + 1) % self.cfg.vocab_size
    return out


def _state_unchanged(self, params, token, cache, **kw):
    logits, _ = DECODE(self, params, token, cache, **kw)
    return logits, cache


def _latents_dropped(self, params, tokens, cache_len):
    logits, cache = PREFILL(self, params, tokens, cache_len)
    return logits, dict(cache, ckv=cache["ckv"] * 0)


FAULTS = {"request_altered": (ServingEngine, "_generate_group", _request_altered),
          "state_unchanged": (Model, "decode_step", _state_unchanged),
          "latents_dropped": (Model, "prefill", _latents_dropped)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_path_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(*FAULTS[fault])
    result, checks = _run()
    assert not result["correct"], checks


def test_the_control_reads_far_above_the_program():
    cell = harness.cell_of(harness.load_spec(), CELL)
    rows = calibrate.readings(cell, CONFIG, TRAFFIC, [3, 4], 0.2)
    for row in rows:
        assert row["control.logit_gap_mean"] > 3 * row["logit_gap_mean"]
        assert row["control.logit_gap_mean"] > 0


def test_the_control_is_not_correct():
    """The control in the program's place fails the cell's own limit."""
    result, checks = _run(seconds=0.01, seed=4, control=True)
    assert not result["correct"], checks
