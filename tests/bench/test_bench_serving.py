"""The qwen2.5-3b serving cells, chat and the prompt-heavy rag, driven
through the harness on the CPU with a two-layer model of the same
architecture, past run.py's look for a chip: a whole run comes out
correct; with the timed path broken underneath, it comes out not correct;
and the control reads far above the program and fails the cell's limit."""

import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import calibrate, harness  # noqa: E402

CONFIG = dict(harness.data("configs", "qwen2.5-3b"), hidden_size=256, intermediate_size=512,
              num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
              vocab_size=1000)
CELLS = {"chat": "qwen2.5-3b-chat", "rag": "qwen2.5-3b-rag"}
TRAFFIC = {
    "chat": dict(harness.data("traffic", "offline-p128-n256"), requests_per_call=4,
                 max_batch=4, prompt_len=6, new_tokens=8, pool=2, sample=64, trace_s=0.01),
    "rag": dict(harness.data("traffic", "offline-p1024-n32"), requests_per_call=4,
                max_batch=4, prompt_len=24, new_tokens=3, pool=2, sample=64, trace_s=0.01),
}


def _run(mix, trace=False, seconds=0.3, seed=2**31 + 99, config=CONFIG, traffic=None,
         control=False):
    return harness.run_cell(CELLS[mix], seed, seconds, trace, t_start=time.perf_counter(),
                            config=config, traffic=traffic or TRAFFIC[mix], cache=False,
                            control=control)


@pytest.mark.parametrize("mix", sorted(TRAFFIC))
@pytest.mark.parametrize("trace", [False, True])
def test_a_run_is_correct(mix, trace):
    result, checks = _run(mix, trace)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] % TRAFFIC[mix]["requests_per_call"] == 0
    assert [c[0] for c in checks] == ["logit_gap"] and checks[0][1] < checks[0][2]
    spec = harness.load_spec()
    want = {m["name"] for m in harness.metrics_for(spec, CELLS[mix], trace)}
    cpu_only = {"idle_share.serve", "decode_step_ms.serve", "mfu.serve"}
    assert want - cpu_only == set(result["metrics"])
    if not trace:
        assert result["metrics"]["tokens_per_s"]["value"] > 0


from repro.models.model import Model  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402

DECODE, GENERATE = Model.decode_step, ServingEngine._generate_group


def _token_altered(self, group):
    out = GENERATE(self, group)
    out[0, 0] = (out[0, 0] + 1) % self.cfg.vocab_size
    return out


def _half_the_batch(self, group):
    half = GENERATE(self, group[:len(group) // 2])
    return np.concatenate([half, half])[:len(group)]


def _state_unchanged(self, params, token, cache, **kw):
    logits, _ = DECODE(self, params, token, cache, **kw)
    return logits, cache


FAULTS = {"token_altered": (ServingEngine, "_generate_group", _token_altered),
          "half_the_batch": (ServingEngine, "_generate_group", _half_the_batch),
          "state_unchanged": (Model, "decode_step", _state_unchanged)}


@pytest.mark.parametrize("mix", sorted(TRAFFIC))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_path_is_not_correct(mix, fault, monkeypatch):
    monkeypatch.setattr(*FAULTS[fault])
    result, checks = _run(mix)
    assert not result["correct"], checks


def test_the_control_reads_far_above_the_program():
    cell = harness.cell_of(harness.load_spec(), CELLS["chat"])
    rows = calibrate.readings(cell, CONFIG, TRAFFIC["chat"], [3, 4], 0.2)
    for row in rows:
        assert row["control.logit_gap"] > 3 * row["logit_gap"]
        assert row["control.logit_gap"] > 0


# The control's gap grows with the width of the logits: two layers 256 wide
# read under the cells' limits, two 2048 wide over them, as the full model
# does on the chip.
WIDE = dict(CONFIG, hidden_size=2048, intermediate_size=2048, num_attention_heads=16,
            vocab_size=8192)
CONTROL_SEED = 4
WIDE_TRAFFIC = {
    "chat": dict(TRAFFIC["chat"], requests_per_call=8, max_batch=8, new_tokens=32),
    "rag": dict(TRAFFIC["rag"], requests_per_call=8, max_batch=8, prompt_len=40, new_tokens=8),
}


@pytest.mark.parametrize("mix", sorted(TRAFFIC))
def test_the_control_is_not_correct(mix):
    """The control in the program's place fails the cell's own limit."""
    result, checks = _run(mix, seconds=0.01, seed=CONTROL_SEED, config=WIDE,
                          traffic=WIDE_TRAFFIC[mix], control=True)
    assert not result["correct"], checks
