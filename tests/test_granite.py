"""Granite-4.0-H's block in the program against the plain reference
(``bench/ref/granite_hybrid.py``), on the CPU in float32 at tiny widths of
the same structure: a Mamba-2 block (8 heads of 16, state 16, one group,
conv 4 with a bias, chunk 8) and a GQA attention block with no position
embedding, each with an MoE of 4 experts (top-2, softmax over the chosen
two, 2 held here from expert 1) and a shared MLP; embeddings times 12,
residual branches times 0.22, logits divided by 2 (at these widths that
gives the logits the spread the cell's published widths give with 16).

Tolerances: float32 throughout, so the program and the reference differ
only in the order of their operations (the chunked scan against the
recurrence, a grouped product against one expert at a time, a softmax
over all experts renormalised against one over the chosen two): 1e-4
relative and 1e-6 absolute on logits whose spread is ~1e-2; 1e-5 between
the prefill's cache and the stepped one, which run the same layers."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]

from bench import generate, harness  # noqa: E402
from bench.paths import serving_hybrid  # noqa: E402
from bench.ref import granite_hybrid as ref  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.utils.params import ParamBuilder  # noqa: E402

TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, intermediate_size=16,
            shared_intermediate_size=32, num_hidden_layers=2, layer_types=["mamba", "attention"],
            num_local_experts=2, first_local_expert=1, num_experts_per_tok=2,
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
            vocab_size=500, torch_dtype="float32", attention_multiplier=0.25, logits_scaling=2,
            published=dict(num_hidden_layers=40, num_local_experts=4,
                           layer_types=["mamba", "attention"] * 20))
CONFIG = dict(harness.data("configs", "granite-4.0-h-small-ep8"), **TINY)
V = TINY["vocab_size"]
LOGITS = dict(rtol=1e-4, atol=1e-6)


@pytest.fixture(scope="module")
def tiny():
    cfg = serving_hybrid.model_config(CONFIG)
    abstract, _ = Model(cfg).init(abstract=True)
    key = jnp.asarray(generate.key_words(11, generate.WEIGHTS))
    return cfg, serving_hybrid.make_weights(abstract, key)


def _tokens(n, s, seed=5):
    return generate.rng(seed, 1).integers(0, V, (n, s)).astype(np.int32)


def _ref_logits(params, tokens, start, count):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(CONFIG, params, tokens, start, count))


def test_model_config_follows_the_file():
    full = serving_hybrid.model_config(harness.data("configs", "granite-4.0-h-small-ep8"))
    assert full.family == "hybrid" and full.num_layers == 10
    assert full.block_pattern == ("mamba",) * 5 + ("attn",) + ("mamba",) * 4
    assert (full.d_model, full.num_heads, full.num_kv_heads, full.head_dim_) == (4096, 32, 8, 128)
    assert (full.rope_theta, full.attention_multiplier, full.local_window) == (0.0, 1 / 128, 0)
    assert (full.ssm_state, full.ssm_head_dim, full.ssm_expand, full.ssm_ngroups,
            full.ssm_conv, full.ssm_chunk, full.ssm_conv_bias) == (128, 64, 2, 1, 4, 256, True)
    assert (full.num_experts, full.experts_held, full.first_expert_held, full.top_k) == \
        (72, 9, 0, 10)
    assert (full.d_ff_expert, full.num_shared_experts, full.norm_topk_prob) == (768, 2, True)
    assert (full.embedding_multiplier, full.residual_multiplier, full.logits_scaling) == \
        (12.0, 0.22, 16.0)
    assert full.tie_embeddings and full.vocab_size == 100352 and full.norm_eps == 1e-5
    # the registered config is the published one, 40 layers of four periods
    published = get_config("granite-4.0-h-small")
    assert dataclasses.replace(published, num_layers=10, experts_held=9) == \
        dataclasses.replace(full, name=published.name, source=published.source)
    model = Model(full)
    abstract, _ = model.init(abstract=True)
    mixer = abstract["super"]["b0_mamba"]["mixer"]
    assert mixer["w_z"].shape == (1, 4096, 8192) and mixer["conv_bias"].shape == (1, 8448,)
    assert abstract["super"]["b5_attn"]["ffn"]["w_in"].shape == (1, 9, 4096, 1536)
    assert abstract["super"]["b5_attn"]["ffn"]["router"].shape == (1, 4096, 72)
    n = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(abstract))
    assert n == pytest.approx(4.83e9, rel=0.005)           # bytes of weights
    cache, _ = model.init_cache(32, 8449, abstract=True)
    assert cache["s0_ssm"].shape == (1, 32, 128, 64, 128) and cache["s0_ssm"].dtype == jnp.float32
    assert cache["s5_k"].shape == (1, 32, 8, 8449, 128)
    assert model.prefill_rows(32, 8192) == 1              # the dispatch operand: 671 MB a row


def test_the_published_layer_count_is_four_periods():
    cfg = get_config("granite-4.0-h-small")
    layer_types = harness.data("configs", "granite-4.0-h-small-ep8")["published"]["layer_types"]
    assert [ref.KINDS[t] for t in layer_types] == list(cfg.block_pattern) * 4
    n = Model(cfg).param_count()
    assert n == pytest.approx(32.2e9, rel=0.01)


def test_forward_matches_the_reference(tiny):
    cfg, params = tiny
    tokens = _tokens(2, 14)
    model = Model(dataclasses.replace(cfg, capacity_factor=64.0))     # no drop in training
    want, _, _ = model.forward(params, {"tokens": jnp.asarray(tokens)}, mode="train")
    np.testing.assert_allclose(np.asarray(want)[..., :V], _ref_logits(params, tokens, 0, 14),
                               **LOGITS)


@pytest.mark.parametrize("impl,blocks", [(None, 1), (None, 2), ("pallas_interpret", 2)])
def test_prefill_then_decode_matches_steps_and_the_reference(tiny, impl, blocks, monkeypatch):
    """``prefill`` of P positions (not a multiple of the chunk) and K decode
    steps give the logits of the reference's full forward at each of those
    positions, and of P teacher-forced steps and K more; the prefill's
    cache is the stepped cache: the SSM state and the conv window whole,
    k/v in slots [0, P) and zeros after them. ``blocks`` 2 runs the prompt
    in two blocks of rows."""
    cfg, params = tiny
    model = Model(cfg)
    assert model.can_prefill
    B, P, K = 2, 13, 3
    if blocks > 1:
        monkeypatch.setattr(M, "PREFILL_BLOCK_BYTES", 1)
    assert model.prefill_rows(B, P) == B // blocks
    tokens = _tokens(B, P + K, seed=6)
    ops.set_default_impl(impl)
    try:
        logits, cache = jax.jit(model.prefill, static_argnums=2)(
            params, jnp.asarray(tokens[:, :P]), P + K + 1)
    finally:
        ops.set_default_impl(None)
    step = jax.jit(model.decode_step)
    stepped, axes = model.init_cache(B, P + K + 1)
    for t in range(P):
        stepped_logits, stepped = step(params, jnp.asarray(tokens[:, t]), stepped)
    assert set(cache) == set(stepped) == {"s0_conv", "s0_ssm", "s1_k", "s1_v", "moe_counts",
                                          "pos"}
    assert int(cache["pos"]) == P
    for name in ("s0_conv", "s0_ssm"):
        assert "kv_seq" not in axes[name]
        np.testing.assert_allclose(np.asarray(cache[name]), np.asarray(stepped[name]),
                                   rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(cache["s0_ssm"])).max() > 1e-3          # a state was carried
    for name in ("s1_k", "s1_v"):
        np.testing.assert_allclose(np.asarray(cache[name][..., :P, :]),
                                   np.asarray(stepped[name][..., :P, :]), rtol=1e-5, atol=1e-5)
        assert not np.asarray(cache[name][..., P:, :]).any()
    # the held experts compute what the steps route to them, nothing dropped
    (routed, most, dropped), (s_routed, s_most, s_dropped) = (
        np.asarray(c["moe_counts"]) for c in (cache, stepped))
    assert routed == s_routed > 0 and dropped == s_dropped == 0 and most >= s_most
    want = _ref_logits(params, tokens, P - 1, K + 1)
    for k in range(K + 1):
        np.testing.assert_allclose(np.asarray(logits[:, :V]), np.asarray(stepped_logits[:, :V]),
                                   **LOGITS)
        np.testing.assert_allclose(np.asarray(logits[:, :V]), want[:, k], **LOGITS)
        if k < K:
            logits, cache = step(params, jnp.asarray(tokens[:, P + k]), cache)
            stepped_logits, stepped = step(params, jnp.asarray(tokens[:, P + k]), stepped)


def test_a_local_window_prefill_fills_the_ring_buffer(tiny):
    """With local attention of 8 slots beside Mamba-2, a prefill of 13
    positions leaves the stepped ring buffer: position p in slot p % 8."""
    cfg, params = tiny
    model = Model(dataclasses.replace(cfg, local_window=8))
    B, P = 2, 13
    tokens = jnp.asarray(_tokens(B, P, seed=8))
    logits, cache = jax.jit(model.prefill, static_argnums=2)(params, tokens, P + 4)
    step = jax.jit(model.decode_step)
    stepped, _ = model.init_cache(B, P + 4)
    for t in range(P):
        stepped_logits, stepped = step(params, tokens[:, t], stepped)
    assert cache["s1_k"].shape[-2] == 8
    for name in set(cache) - {"pos", "moe_counts"}:
        np.testing.assert_allclose(np.asarray(cache[name]), np.asarray(stepped[name]),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(stepped_logits), **LOGITS)


def test_prefill_blocks_follow_the_widest_per_token_temporary():
    """Granite's widest per-token temporary is the expert dispatch operand
    (10 rows of 4096 a token), wider than its Mamba-2 in-projection
    (16,768) and q, k, v (6,144); DeepSeek-V2's expanded attention and
    qwen2.5-3b's q, k and v still set theirs."""
    granite = Model(get_config("granite-4.0-h-small"))
    assert granite.prefill_rows(32, 8192) == 1
    assert granite.prefill_rows(32, 1024) == 8             # 84 MB a row: 12, then 8 divides
    no_experts = Model(dataclasses.replace(get_config("granite-4.0-h-small"), num_experts=0))
    assert no_experts.prefill_rows(32, 1024) == 16         # 34 MB a row of in-projection
    assert Model(get_config("deepseek-v2-236b")).prefill_rows(128, 1024) == 8
    assert Model(get_config("qwen2.5-3b")).prefill_rows(32, 1024) == 32


# --- the expert layer ---------------------------------------------------------

LAYER = dataclasses.replace(serving_hybrid.model_config(CONFIG), experts_held=0,
                            first_expert_held=0)


def _layer(cfg, seed=2):
    b = ParamBuilder(jax.random.PRNGKey(seed), dtype=jnp.float32)
    MOE.init_moe(b, "ffn", cfg)
    p = b.build()[0]["ffn"]
    p["router"] = p["router"] * 8.0          # spread the scores
    return p


def _x(seed=3, n=2, s=8):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, s, LAYER.d_model), jnp.float32)


def _shared(p, x):
    h = (x @ p["w_shared_up"]) * jax.nn.silu(x @ p["w_shared_gate"])
    return h @ p["w_shared_out"]


def test_the_shares_of_every_chip_add_up_to_the_uncut_layer():
    """Two chips of two experts each: their parts, with the shared MLP that
    each computes counted once, give the uncut layer, and that layer is the
    reference's routing (softmax over the top-2 logits) applied expert by
    expert."""
    p, x = _layer(LAYER), _x()
    whole, _ = MOE.serve_moe(p, x, LAYER)
    per, chips = 2, LAYER.num_experts // 2
    parts = []
    for c in range(chips):
        cfg = dataclasses.replace(LAYER, experts_held=per, first_expert_held=c * per)
        share = dict(p, w_in=p["w_in"][c * per:(c + 1) * per],
                     w_out=p["w_out"][c * per:(c + 1) * per])
        y, _ = MOE.serve_moe(share, x, cfg)
        parts.append(np.asarray(y))
    total = sum(parts) - (chips - 1) * np.asarray(_shared(p, x))
    np.testing.assert_allclose(total, np.asarray(whole), rtol=2e-5, atol=2e-5)
    xf = x.reshape(-1, LAYER.d_model)
    weights = np.asarray(ref.route(xf @ p["router"], LAYER.top_k, LAYER.num_experts))
    assert ((weights > 0).sum(1) == LAYER.top_k).all()
    want = np.array(_shared(p, xf))
    for e in range(LAYER.num_experts):
        u, g = np.split(np.asarray(xf @ p["w_in"][e]), 2, axis=-1)
        want += weights[:, e:e + 1] * ((u * np.asarray(jax.nn.silu(g))) @ np.asarray(p["w_out"][e]))
    np.testing.assert_allclose(np.asarray(whole).reshape(want.shape), want, rtol=2e-5, atol=2e-5)


def test_routing_is_a_softmax_over_the_chosen_logits():
    p, x = _layer(LAYER, seed=4), _x(seed=5, n=4, s=16)
    logits = x.reshape(-1, LAYER.d_model) @ p["router"]
    _, w, idx = MOE.route(logits, LAYER)
    top, _ = jax.lax.top_k(logits, LAYER.top_k)
    np.testing.assert_allclose(np.asarray(w), np.asarray(jax.nn.softmax(top, -1)), rtol=1e-6)
    dense = np.asarray(ref.route(logits, LAYER.top_k, LAYER.num_experts))
    np.testing.assert_allclose(np.take_along_axis(dense, np.asarray(idx), 1), np.asarray(w),
                               rtol=1e-6)
