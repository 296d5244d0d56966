"""DeepSeek-V2's block in the program against the plain reference
(``bench/ref/deepseek_v2.py``) and against per-token oracles, on the CPU in
float32 at tiny widths of the same structure: MLA with q and kv LoRA and
YaRN rope, a dense layer 0, group-limited routing over 4 groups of 2
experts, 4 of the 8 routed experts held here, 2 shared experts.

Tolerances: float32 throughout, so the program and the reference differ
only by the order of their operations (absorbed against expanded MLA, a
grouped product against one expert at a time, blocked against whole
attention): 1e-4 on logits of magnitude ~4, 2e-5 on one layer's output."""

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
from bench.paths import serving_moe  # noqa: E402
from bench.ref import deepseek_v2 as ref  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.utils.params import ParamBuilder  # noqa: E402

TINY = dict(hidden_size=64, intermediate_size=96, num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8, moe_intermediate_size=16, n_group=4,
            topk_group=2, num_experts_per_tok=3, n_routed_experts=4, first_routed_expert=2,
            vocab_size=500, torch_dtype="float32",
            published=dict(num_hidden_layers=60, n_routed_experts=8))
CONFIG = dict(harness.data("configs", "deepseek-v2-ep8"), **TINY)
V = TINY["vocab_size"]


@pytest.fixture(scope="module")
def tiny():
    cfg = serving_moe.model_config(CONFIG)
    abstract, _ = Model(cfg).init(abstract=True)
    key = jnp.asarray(generate.key_words(11, generate.WEIGHTS))
    return cfg, serving_moe.make_weights(abstract, key)


def _tokens(n, s, seed=5):
    return generate.rng(seed, 1).integers(0, V, (n, s)).astype(np.int32)


def _ref_logits(params, tokens, start, count):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(CONFIG, params, tokens, start, count))


def test_model_config_follows_the_file():
    full = serving_moe.model_config(harness.data("configs", "deepseek-v2-ep8"))
    assert (full.num_layers, full.first_dense_layers, full.d_model, full.num_heads) == \
        (6, 1, 5120, 128)
    assert (full.num_experts, full.experts_held, full.first_expert_held, full.top_k) == \
        (160, 20, 0, 6)
    assert (full.n_group, full.topk_group, full.norm_topk_prob,
            full.routed_scaling_factor) == (8, 3, False, 16.0)
    assert (full.kv_lora_rank, full.q_lora_rank, full.qk_nope_head_dim,
            full.qk_rope_head_dim, full.v_head_dim) == (512, 1536, 128, 64, 128)
    assert (full.rope_factor, full.rope_mscale_all_dim, full.norm_eps) == (40.0, 0.707, 1e-6)
    assert full.d_ff == 12288 and full.d_ff_expert == 1536 and not full.tie_embeddings
    abstract, _ = Model(full).init(abstract=True)
    assert abstract["blocks"]["ffn"]["router"].shape == (5, 5120, 160)
    assert abstract["blocks"]["ffn"]["w_in"].shape == (5, 20, 5120, 3072)
    n = sum(a.size for a in jax.tree.leaves(abstract))
    assert n * 2 == pytest.approx(9.47e9, rel=0.005)       # bytes of bf16 weights


def test_yarn_matches_the_published_numbers():
    from repro.models import mla
    full = serving_moe.model_config(harness.data("configs", "deepseek-v2-ep8"))
    mscale = 0.1 * 0.707 * np.log(40) + 1
    assert mla.softmax_scale(full) == pytest.approx(mscale ** 2 / np.sqrt(192))
    assert mscale ** 2 == pytest.approx(1.590, abs=1e-3)
    want = ref.yarn_inv_freq(harness.data("configs", "deepseek-v2-ep8"))
    np.testing.assert_allclose(mla.rope_freqs(full), want, rtol=1e-12)
    # the fast dims keep theta's frequencies, the slow ones are divided by 40
    plain = 1.0 / 10000 ** (np.arange(0, 64, 2) / 64)
    assert want[0] == plain[0] and want[-1] == pytest.approx(plain[-1] / 40)


def test_forward_matches_the_reference(tiny):
    cfg, params = tiny
    tokens = _tokens(2, 14)
    model = Model(dataclasses.replace(cfg, capacity_factor=64.0))     # no drop in training
    want, _, _ = model.forward(params, {"tokens": jnp.asarray(tokens)}, mode="train")
    np.testing.assert_allclose(np.asarray(want)[..., :V], _ref_logits(params, tokens, 0, 14),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl,blocks", [(None, 1), (None, 2), ("pallas_interpret", 2)])
def test_prefill_then_decode_matches_steps_and_the_reference(tiny, impl, blocks, monkeypatch):
    """``prefill`` of P positions and K decode steps give the logits of P
    teacher-forced steps and K more, and of the reference's full forward;
    the prefill's latent cache is the stepped cache. ``blocks`` 2 runs the
    prompt in two blocks of rows."""
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
    stepped, _ = model.init_cache(B, P + K + 1)
    for t in range(P):
        stepped_logits, stepped = step(params, jnp.asarray(tokens[:, t]), stepped)
    assert set(cache) == set(stepped) == {"ckv", "krope", "d_ckv", "d_krope", "moe_counts", "pos"}
    assert int(cache["pos"]) == P
    for name in ("ckv", "krope", "d_ckv", "d_krope"):
        np.testing.assert_allclose(np.asarray(cache[name][..., :P, :]),
                                   np.asarray(stepped[name][..., :P, :]), rtol=1e-5, atol=1e-5)
        assert not np.asarray(cache[name][..., P:, :]).any()
    # the held experts compute the assignments the steps route to them, and
    # nothing is dropped; one pass over P positions loads an expert more
    # than one step does
    (routed, most, dropped), (s_routed, s_most, s_dropped) = (
        np.asarray(c["moe_counts"]) for c in (cache, stepped))
    assert routed == s_routed > 0 and dropped == s_dropped == 0 and most >= s_most
    want = _ref_logits(params, tokens, P - 1, K + 1)
    for k in range(K + 1):
        np.testing.assert_allclose(np.asarray(logits[:, :V]), np.asarray(stepped_logits[:, :V]),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(logits[:, :V]), want[:, k], rtol=1e-4, atol=1e-4)
        if k < K:
            logits, cache = step(params, jnp.asarray(tokens[:, P + k]), cache)
            stepped_logits, stepped = step(params, jnp.asarray(tokens[:, P + k]), stepped)


# --- the expert layer ---------------------------------------------------------

LAYER = dataclasses.replace(
    serving_moe.model_config(CONFIG), experts_held=0, first_expert_held=0)


def _layer(cfg, seed=2):
    b = ParamBuilder(jax.random.PRNGKey(seed), dtype=jnp.float32)
    MOE.init_moe(b, "ffn", cfg)
    p = b.build()[0]["ffn"]
    p["router"] = p["router"] * 8.0          # spread the scores
    return p


def _x(seed=3, n=2, s=8):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, s, LAYER.d_model), jnp.float32)


def test_the_shares_of_every_group_add_up_to_the_uncut_layer():
    p, x = _layer(LAYER), _x()
    whole, _ = MOE.serve_moe(p, x, LAYER)
    per = LAYER.num_experts // LAYER.n_group
    parts = []
    for g in range(LAYER.n_group):
        cfg = dataclasses.replace(LAYER, experts_held=per, first_expert_held=g * per)
        share = dict(p, w_in=p["w_in"][g * per:(g + 1) * per],
                     w_out=p["w_out"][g * per:(g + 1) * per])
        y, _ = MOE.serve_moe(share, x, cfg)
        parts.append(np.asarray(y))
    # the shared experts, which every share computes, counted once
    total = sum(parts) - (LAYER.n_group - 1) * np.asarray(_shared(p, x))
    np.testing.assert_allclose(total, np.asarray(whole), rtol=2e-5, atol=2e-5)


def _shared(p, x):
    h = (x @ p["w_shared_up"]) * jax.nn.silu(x @ p["w_shared_gate"])
    return h @ p["w_shared_out"]


def _oracle_route(probs, cfg):
    """Per token: the topk_group groups of the best scores, then top_k
    inside them; weights times the scaling factor."""
    E, G = probs.shape[1], cfg.n_group
    out = []
    for row in probs:
        best = row.reshape(G, E // G).max(1)
        groups = np.argsort(-best, kind="stable")[:cfg.topk_group]
        allowed = np.concatenate([np.arange(g * (E // G), (g + 1) * (E // G)) for g in groups])
        chosen = allowed[np.argsort(-row[allowed], kind="stable")[:cfg.top_k]]
        out.append({int(e): float(row[e]) * cfg.routed_scaling_factor for e in chosen})
    return out


def test_group_limited_routing_matches_a_per_token_oracle():
    p, x = _layer(LAYER, seed=4), _x(seed=5, n=4, s=16)
    logits = x.reshape(-1, LAYER.d_model) @ p["router"]
    probs, w, idx = MOE.route(logits, LAYER)
    oracle = _oracle_route(np.asarray(probs), LAYER)
    groups_used = set()
    for t, want in enumerate(oracle):
        got = {int(e): float(v) for e, v in zip(idx[t], w[t])}
        assert got.keys() == want.keys()
        for e in want:
            assert got[e] == pytest.approx(want[e], rel=1e-6)
        groups_used.add(frozenset(e // 2 for e in want))
    assert len(groups_used) > 1                          # the routing is exercised
    # the reference routes alike
    weights, chosen = ref.route(dict(n_group=LAYER.n_group, topk_group=LAYER.topk_group,
                                     top_k=LAYER.top_k, scaling=16.0), probs)
    assert [set(map(int, c)) for c in chosen] == [set(o) for o in oracle]


def _oracle_layer(p, x, cfg):
    xf = np.asarray(x.reshape(-1, cfg.d_model))
    probs = np.asarray(jax.nn.softmax(xf @ np.asarray(p["router"]), -1))
    y = np.array(_shared(p, jnp.asarray(xf)))
    for t, chosen in enumerate(_oracle_route(probs, cfg)):
        for e, w in chosen.items():
            j = e - cfg.first_expert_held
            if 0 <= j < cfg.num_experts_held:
                u, g = np.split(xf[t] @ np.asarray(p["w_in"][j]), 2)
                y[t] += w * ((u * np.asarray(jax.nn.silu(g))) @ np.asarray(p["w_out"][j]))
    return y


def test_routing_skewed_onto_one_expert_drops_nothing():
    """Most tokens choose expert 3, far past its capacity: training clips
    it there, the serving path computes all of its tokens."""
    cfg = dataclasses.replace(LAYER, experts_held=4, first_expert_held=2)
    p, x = _layer(cfg, seed=6), _x(seed=7, n=2, s=16).at[..., 0].add(3.0)
    p["router"] = p["router"].at[:, 3].set(0.0).at[0, 3].set(3.0)
    y, counts = MOE.serve_moe(p, x, cfg)
    want = _oracle_layer(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y).reshape(want.shape), want, rtol=2e-5, atol=2e-5)
    T = x.shape[0] * x.shape[1]
    capacity = max(4, int(T * cfg.top_k / cfg.num_experts * cfg.capacity_factor) + 1)
    routed, most, dropped = (int(c) for c in counts)
    assert most > 1.5 * capacity and dropped == 0
    probs = np.asarray(jax.nn.softmax(np.asarray(x.reshape(T, -1)) @ np.asarray(p["router"])))
    held = sum(2 <= e < 6 for r in _oracle_route(probs, cfg) for e in r)
    assert routed == held
    clipped, _ = MOE.apply_moe(p, x, cfg)
    assert not np.allclose(np.asarray(clipped).reshape(want.shape), want, atol=1e-3)
