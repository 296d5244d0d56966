"""``run_range`` runs a MobileNetV2 stage as one compiled program: the same
numbers as a per-leaf eager loop, one program traced per stage and shape,
the weights read afresh on every call, and the ``amp4ec.stage_program``
span only while the recorder is on."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.models.mobilenetv2 import build_mobilenetv2, run_full, run_range
from repro.utils import obs

IMAGE = 32


def eager(leaves, lo, hi, x, residual=None):
    """The reference: one eager ``leaf.apply`` at a time."""
    for leaf in leaves[lo:hi]:
        if leaf.save_residual:
            residual = x
        x, residual = leaf.apply(leaf.params, x, residual)
        if leaf.add_residual:
            x = x + residual
            residual = None
    return x, residual


def rel(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    return float(np.abs(got - want).max() / np.abs(want).max())


def images(batch, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (batch, IMAGE, IMAGE, 3))


@pytest.fixture(scope="module")
def leaves():
    return build_mobilenetv2(jax.random.PRNGKey(7))


def _inside_a_block(leaves):
    """A cut two leaves into the first block whose input is added back."""
    return next(i for i, leaf in enumerate(leaves) if leaf.save_residual) + 2


@pytest.mark.parametrize("case", ["first_stage", "residual_across_the_cut", "last_stage",
                                  "run_full"])
def test_run_range_matches_the_eager_loop(leaves, case):
    x = images(2)
    if case == "run_full":
        assert rel(run_full(leaves, x), eager(leaves, 0, len(leaves), x)[0]) <= 1e-6
        return
    cut = _inside_a_block(leaves)
    ranges = {"first_stage": [(0, 40)],
              "residual_across_the_cut": [(0, cut), (cut, len(leaves))],
              "last_stage": [(0, 120), (120, len(leaves))]}[case]
    h, res = x, None
    for k, (lo, hi) in enumerate(ranges):
        got = run_range(leaves, lo, hi, h, res)
        h, res = eager(leaves, lo, hi, h, res)
        assert rel(got[0], h) <= 1e-6
        assert (got[1] is None) == (res is None)
        if res is not None:
            assert rel(got[1], res) <= 1e-6
        if case == "residual_across_the_cut" and k == 0:
            assert res is not None                 # the cut falls inside a block


def _built(leaves, lo, hi, x):
    obs.enable()
    try:
        y, _ = run_range(leaves, lo, hi, x)
        (sp,) = [s for s in obs.snapshot()["spans"] if s.name == "amp4ec.stage_program"]
    finally:
        obs.disable()
    assert (sp.attrs["lo"], sp.attrs["hi"]) == (lo, hi)
    return sp.attrs["built"], y


@pytest.mark.parametrize("batches, built", [([2, 2], [1, 0]), ([2, 3], [1, 1])],
                         ids=["same_range_again", "new_batch_size"])
def test_built_counts_the_programs_traced_in_the_call(batches, built):
    fresh = build_mobilenetv2(jax.random.PRNGKey(11))      # leaf functions no program has
    assert [_built(fresh, 0, 12, images(b))[0] for b in batches] == built


def _shifted_bias(leaf):
    return {"params": dict(leaf.params, bias=leaf.params["bias"] + 1.0)}


def _identity(leaf):
    return {"apply": lambda p, x, r: (x, r)}


@pytest.mark.parametrize("change, built", [(_shifted_bias, 0), (_identity, 1)],
                         ids=["new_params_reuse_the_program", "new_apply_builds_a_new_program"])
def test_a_rebuilt_leaf_changes_the_output(leaves, change, built):
    """Nothing is stale: new weights go into the same program, a new leaf
    function into a new one, and either way the output follows."""
    x = images(2)
    _, y = _built(leaves, 0, 12, x)
    i = next(i for i, leaf in enumerate(leaves[:12]) if leaf.kind == "BatchNorm2d")
    changed = list(leaves)
    changed[i] = dataclasses.replace(leaves[i], **change(leaves[i]))
    got, y2 = _built(changed, 0, 12, x)
    assert got == built
    assert rel(y2, eager(changed, 0, 12, x)[0]) <= 1e-6
    assert not np.allclose(np.asarray(y2), np.asarray(y))


def test_no_span_while_the_recorder_is_off(leaves):
    obs.enable()
    obs.disable()
    run_range(leaves, 0, 12, images(2))
    assert not obs.enabled() and obs.snapshot()["spans"] == []
