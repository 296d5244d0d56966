"""Each plain reference in bench/ref/ against the program at a tiny size on
the CPU, in float32, on the benchmark's own seeded weights."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import generate, harness  # noqa: E402
from bench.paths import partitioned, serving  # noqa: E402
from bench.ref import mobilenetv2 as ref_mnv2  # noqa: E402
from bench.ref import qwen2 as ref_qwen  # noqa: E402

TINY_QWEN = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2, vocab_size=500,
                 torch_dtype="float32")


def _key(seed):
    return jnp.asarray(generate.key_words(seed, generate.WEIGHTS))


@pytest.mark.parametrize("image", [32, 35])
def test_mobilenetv2_reference_matches_the_program(image):
    from repro.models.mobilenetv2 import run_full
    config = dict(harness.data("configs", "mobilenetv2-224"), image_size=image)
    params = ref_mnv2.init_params(config, _key(5))
    assert len(params) == len([x for x in ref_mnv2.layers(config)]) == 105
    leaves = partitioned.program_leaves(config, params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, image, image, 3))
    want = np.asarray(run_full(leaves, x))
    got = np.asarray(ref_mnv2.forward(config, params, x))
    assert got.shape == (2, 1000)
    # float32 round-off of two operation orders, grown through 52 batch norms
    assert partitioned.rel_err(want, got) < 1e-4


def test_mobilenetv2_leaves_refuse_another_schedule():
    config = harness.data("configs", "mobilenetv2-224")
    other = dict(config, last_channels=1024)
    with pytest.raises(ValueError):
        partitioned.program_leaves(other, ref_mnv2.init_params(config, _key(1)))


def _qwen(seed=3):
    from repro.models.model import Model
    config = dict(harness.data("configs", "qwen2.5-3b"), **TINY_QWEN)
    cfg = serving.model_config(config)
    abstract, _ = Model(cfg).init(abstract=True)
    return config, cfg, serving.make_weights(abstract, _key(seed))


def test_qwen_model_config_follows_the_file():
    config, cfg, params = _qwen()
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.d_ff) == \
        (2, 64, 4, 2, 128)
    assert cfg.norm_eps == 1e-6 and cfg.rope_theta == 1e6 and cfg.qkv_bias
    assert cfg.tie_embeddings and cfg.dtype == "float32"
    full = serving.model_config(harness.data("configs", "qwen2.5-3b"))
    assert (full.num_layers, full.d_model, full.d_ff, full.vocab_size, full.dtype) == \
        (36, 2048, 11008, 151936, "bfloat16")
    biases = params["blocks"]["attn"]["b_q"]
    assert float(jnp.abs(biases).max()) > 0            # the biases are exercised


def test_qwen_reference_matches_the_program_forward():
    from repro.models.model import Model
    config, cfg, params = _qwen()
    tokens = generate.rng(4, 1).integers(0, config["vocab_size"], (2, 12)).astype(np.int32)
    want, _, _ = Model(cfg).forward(params, {"tokens": jnp.asarray(tokens)}, mode="train")
    want = np.asarray(want)[..., :config["vocab_size"]]
    got = np.asarray(ref_qwen.logits(config, params, tokens, 0, 12))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_qwen_reference_agrees_with_served_tokens():
    from repro.core import make_paper_cluster
    config, cfg, params = _qwen(seed=8)
    from repro.serving import ServingEngine
    engine = ServingEngine(cfg, params, make_paper_cluster(), max_batch=3)
    prompts = generate.rng(9, 1).integers(0, config["vocab_size"], (3, 6)).astype(np.int32)
    served = serving._call(engine, prompts, 5)
    tokens = np.concatenate([prompts, served[:, :-1]], 1).astype(np.int32)
    gaps = np.asarray(ref_qwen.gaps(config, params, tokens, served.astype(np.int32), 6))
    assert gaps.shape == (3, 5) and gaps.max() < 1e-4
    # a served token moved off the argmax opens a gap
    wrong = served.copy()
    wrong[1, 2] = (wrong[1, 2] + 1) % config["vocab_size"]
    gaps = np.asarray(ref_qwen.gaps(config, params, tokens, wrong.astype(np.int32), 6))
    assert gaps[1, 2] > 1e-3 and gaps[0].max() < 1e-4
