"""Which path ``ServingEngine`` takes through a group's prompt: one jitted
prefill where the model ``can_prefill``, else one decode step a prompt
position; and that both serve the same tokens."""

import jax
import numpy as np
import pytest

from conftest import f32_reduced
from repro.core.cluster import make_paper_cluster
from repro.models.model import Model
from repro.serving import Request, ServingEngine
from repro.serving.engine import measured_ms
from repro.utils import obs


def _serve(cfg, params, lengths, new_tokens=4):
    """Tokens served for seeded prompts of ``lengths``, and the spans."""
    engine = ServingEngine(cfg, params, make_paper_cluster(), max_batch=2)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(1, cfg.vocab_size, p, dtype=np.int32), new_tokens)
            for i, p in enumerate(lengths)]
    obs.enable()
    try:
        engine.serve(reqs)
    finally:
        obs.disable()
    return np.concatenate([r.output for r in reqs]), obs.snapshot()


def _count(snap, name):
    return sum(s.name == name for s in snap["spans"])


def test_prefill_and_stepped_prompt_serve_the_same_tokens(monkeypatch):
    cfg = f32_reduced("qwen2.5-3b")
    params, _ = Model(cfg).init(jax.random.PRNGKey(0))
    lengths = (7, 7, 12, 12, 12)             # three groups of two lengths
    prefilled, snap = _serve(cfg, params, lengths)
    assert _count(snap, "amp4ec.prefill") == 3
    assert measured_ms(snap)["prefill_share"] == 1.0
    monkeypatch.setattr(Model, "can_prefill", property(lambda self: False))
    stepped, snap = _serve(cfg, params, lengths)
    assert _count(snap, "amp4ec.prefill") == 0
    assert measured_ms(snap)["prefill_share"] == 0.0
    np.testing.assert_array_equal(prefilled, stepped)


@pytest.mark.parametrize("arch", ["recurrentgemma-9b", "mamba2-130m"])
def test_moe_and_ssm_step_through_the_prompt(arch, monkeypatch):
    """An RG-LRU hybrid steps through the prompt. A Mamba-2 stack prefills
    it, and serves the tokens that stepping through it serves."""
    cfg = f32_reduced(arch)
    prefills = arch == "mamba2-130m"
    assert Model(cfg).can_prefill == prefills
    params, _ = Model(cfg).init(jax.random.PRNGKey(0))
    P, N = 6, 3
    out, snap = _serve(cfg, params, (P, P), new_tokens=N)
    assert out.shape == (2 * N,)
    assert _count(snap, "amp4ec.prefill") == int(prefills)
    assert _count(snap, "amp4ec.step") == (N - 1 if prefills else P + N - 1)
    prompt, = (s for s in snap["spans"] if s.name == "amp4ec.prompt")
    assert prompt.attrs == (dict(prefilled=2 * P, stepped=0) if prefills
                            else dict(prefilled=0, stepped=2 * P))
    if prefills:
        monkeypatch.setattr(Model, "can_prefill", property(lambda self: False))
        stepped, snap = _serve(cfg, params, (P, P), new_tokens=N)
        assert _count(snap, "amp4ec.prefill") == 0
        np.testing.assert_array_equal(out, stepped)
