"""The partitioned MobileNetV2 cell driven through the harness on the CPU
at a tiny image size, past run.py's look for a chip, in its closed loop and
in the open loop of `poisson-b1`: a whole run comes out correct; with the
timed path broken underneath, it comes out not correct; and the control
reads far above the program."""

import dataclasses
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import calibrate, harness  # noqa: E402

CONFIG = dict(harness.data("configs", "mobilenetv2-224"), image_size=32)
CELL = "mnv2-b32-closed"
TRAFFIC = {
    "closed": dict(harness.data("traffic", "closed-b32"), batch=4, pool=2, sample=3, trace_s=0.2),
    "open": dict(harness.data("traffic", "poisson-b1"), pool=3, sample=6, rate_per_s=12.0,
                 trace_s=0.2),
}


@pytest.fixture(autouse=True)
def _keep_matmul_precision():
    """The path sets JAX's default matmul precision for its run; restore it."""
    import jax
    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)


def _run(mix, trace=False, seconds=0.6, seed=2**31 + 7, control=False):
    return harness.run_cell(CELL, seed, seconds, trace, t_start=time.perf_counter(),
                            config=CONFIG, traffic=TRAFFIC[mix], cache=False, control=control)


@pytest.mark.parametrize("mix", sorted(TRAFFIC))
@pytest.mark.parametrize("trace", [False, True])
def test_a_run_is_correct(mix, trace):
    result, checks = _run(mix, trace)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    assert [c[0] for c in checks] == ["logit_rel_err"] and checks[0][1] < 1e-4
    spec = harness.load_spec()
    want = {m["name"] for m in harness.metrics_for(spec, CELL, trace)}
    # on the CPU the device-trace and peak readers find nothing to read
    cpu_only = {"idle_share.closed", "mfu.closed"}
    assert want - cpu_only == set(result["metrics"])
    assert list(result)[-1] == "checks"
    if trace:
        assert result["device"]["window_s"] > 0 and "breakdown" in result
    if mix == "open":
        assert result["attempted"] == round(12.0 * 0.6)


def _last_stage(fn):
    def run_range(leaves, lo, hi, x, residual=None):
        y, res = ORIGINAL(leaves, lo, hi, x, residual)
        return (fn(y) if hi == len(leaves) else y), res
    return run_range


def _skip_a_leaf(leaves, lo, hi, x, residual=None):
    """A batch norm in the middle that returns its input unchanged."""
    leaves = list(leaves)
    i = next(i for i, leaf in enumerate(leaves) if i > 40 and leaf.kind == "BatchNorm2d")
    leaves[i] = dataclasses.replace(leaves[i], apply=lambda p, x, r: (x, r))
    return ORIGINAL(leaves, lo, hi, x, residual)


from repro.models import mobilenetv2 as program  # noqa: E402

ORIGINAL = program.run_range
FAULTS = {
    "answer_altered": _last_stage(lambda y: y.at[0, 3].add(1.0)),
    "half_the_batch": _last_stage(lambda y: y.at[y.shape[0] // 2:].set(y[:y.shape[0] // 2])),
    "state_unchanged": _skip_a_leaf,
}


@pytest.mark.parametrize("mix, fault", [
    ("closed", "answer_altered"), ("closed", "half_the_batch"), ("closed", "state_unchanged"),
    ("open", "answer_altered"), ("open", "state_unchanged")])
def test_a_broken_path_is_not_correct(mix, fault, monkeypatch):
    monkeypatch.setattr(program, "run_range", FAULTS[fault])
    result, checks = _run(mix)
    assert not result["correct"], checks


def test_the_control_reads_far_above_the_program():
    cell = harness.cell_of(harness.load_spec(), CELL)
    rows = calibrate.readings(cell, CONFIG, TRAFFIC["closed"], [3, 4], 0.3)
    for row in rows:
        # the CPU computes float32 whatever the precision: the program reads
        # round-off, the three-pass control far more
        assert row["control.logit_rel_err"] > 10 * row["logit_rel_err"]
        assert row["control.logit_rel_err"] > 1e-5


@pytest.mark.parametrize("mix", sorted(TRAFFIC))
def test_the_control_is_not_correct(mix):
    """The control in the program's place fails the cell's own limit."""
    result, checks = _run(mix, control=True)
    assert not result["correct"], checks
