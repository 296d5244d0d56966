"""Per-architecture smoke tests (reduced variants) + cross-mode consistency.

Smoke: every assigned arch instantiates its reduced config (2 layers,
d_model <= 512, <= 4 experts), runs one forward/train step and one decode
step on CPU; asserts output shapes and finiteness.

Consistency: sequential decode (cache path) must reproduce the full forward
(train path) logits — run in float32 per family.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models.model import Model

BATCH, SEQ = 2, 32


def _batch_for(cfg, rng, batch=BATCH, seq=SEQ):
    out = {"tokens": jax.random.randint(rng, (batch, seq + 1), 0, cfg.vocab_size)}
    if cfg.family == "audio":
        out["frames"] = jax.random.normal(rng, (batch, cfg.num_frames, cfg.d_model),
                                          jnp.float32).astype(cfg.jnp_dtype)
    if cfg.family == "vlm":
        out["images"] = jax.random.normal(rng, (batch, cfg.num_image_tokens, cfg.d_model),
                                          jnp.float32).astype(cfg.jnp_dtype)
    return out


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_smoke_train_step(arch, rng):
    cfg = get_config(arch).reduced()
    assert cfg.num_layers == 2 and cfg.d_model <= 512
    if cfg.num_experts:
        assert cfg.num_experts <= 4
    model = Model(cfg)
    params, specs = model.init(rng)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, tuple))
    batch = _batch_for(cfg, rng)

    logits, aux, _ = jax.jit(
        lambda p, b: model.forward(p, b, mode="train"))(params, batch)
    assert logits.shape == (BATCH, SEQ + 1, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()

    loss, nll = jax.jit(lambda p, b: model.loss_fn(p, b, remat=True))(params, batch)
    assert np.isfinite(float(loss)) and float(nll) > 0

    # one actual gradient step
    grads = jax.jit(jax.grad(lambda p, b: model.loss_fn(p, b, remat=False)[0])
                    )(params, batch)
    gnorm = float(jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                               for g in jax.tree.leaves(grads))))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_smoke_decode_step(arch, rng):
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params, _ = model.init(rng)
    cache, specs = model.init_cache(BATCH, 64)
    assert jax.tree.structure(cache) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, tuple))
    if cfg.family in ("audio", "vlm"):
        mem = jnp.zeros((BATCH,
                         cfg.num_frames if cfg.family == "audio" else cfg.num_image_tokens,
                         cfg.d_model), cfg.jnp_dtype)
        cache = model.fill_cross_cache(params, cache, mem)
    tok = jnp.zeros((BATCH,), jnp.int32)
    logits, cache2 = jax.jit(model.decode_step)(params, tok, cache)
    assert logits.shape == (BATCH, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert int(cache2["pos"]) == int(cache["pos"]) + 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_forward(arch, rng):
    """Sequential decode logits == full-forward logits at every position.

    MoE archs use a high capacity factor: the forward pass drops tokens at
    capacity while single-token decode never does, so consistency holds only
    in the drop-free regime.
    """
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32",
                              capacity_factor=16.0)
    model = Model(cfg)
    params, _ = model.init(rng)
    batch = _batch_for(cfg, rng, batch=1, seq=16)
    tokens = batch["tokens"][:, :16]

    fwd_logits, _, _ = model.forward(params, {**batch, "tokens": tokens},
                                     mode="train")
    cache, _ = model.init_cache(1, 32)
    if cfg.family == "audio":
        cache = model.fill_cross_cache(params, cache, batch["frames"])
    if cfg.family == "vlm":
        cache = model.fill_cross_cache(params, cache, batch["images"])
    step = jax.jit(model.decode_step)
    errs = []
    for t in range(16):
        logits, cache = step(params, tokens[:, t], cache)
        errs.append(float(jnp.max(jnp.abs(
            logits[0, :cfg.vocab_size]
            - fwd_logits[0, t, :cfg.vocab_size]))))
    assert max(errs) < 2e-3, f"{arch}: max dec-vs-fwd err {max(errs)}"


def test_sliding_window_decode_matches_windowed_forward(rng):
    """Ring-buffer sliding decode == full forward with the same window."""
    cfg = dataclasses.replace(get_config("yi-9b").reduced(), dtype="float32",
                              window=8)
    model = Model(cfg)
    params, _ = model.init(rng)
    T = 20
    tokens = jax.random.randint(rng, (1, T), 0, cfg.vocab_size)
    fwd_logits, _, _ = model.forward(params, {"tokens": tokens}, mode="train",
                                     window=8)
    cache, _ = model.init_cache(1, 8)   # ring buffer of window size
    step = jax.jit(lambda p, t, c: model.decode_step(p, t, c, window=8))
    for t in range(T):
        logits, cache = step(params, tokens[:, t], cache)
        err = float(jnp.max(jnp.abs(logits[0, :cfg.vocab_size]
                                    - fwd_logits[0, t, :cfg.vocab_size])))
        assert err < 2e-3, f"pos {t}: err {err}"


def test_moe_aux_loss_nonzero(rng):
    cfg = get_config("kimi-k2-1t-a32b").reduced()
    model = Model(cfg)
    params, _ = model.init(rng)
    batch = _batch_for(cfg, rng)
    _, aux, _ = model.forward(params, batch, mode="train")
    assert float(aux) > 0.0   # load-balance loss is active


def test_param_counts_full_configs():
    """Full-config parameter counts are in the right ballpark (abstract init)."""
    expect = {
        "qwen2-7b": (6e9, 9e9),
        "yi-9b": (8e9, 10e9),
        "kimi-k2-1t-a32b": (0.9e12, 1.2e12),
        "deepseek-v2-236b": (2.0e11, 2.6e11),
        "mamba2-130m": (1.0e8, 1.8e8),
        "llama-3.2-vision-90b": (8e10, 1.1e11),
    }
    for arch, (lo, hi) in expect.items():
        model = Model(get_config(arch))
        n = model.param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B params out of range"


def test_moe_dispatch_matches_per_token_oracle(rng):
    """Sort-based capacity dispatch == per-token dense oracle (no drops)."""
    import jax.numpy as jnp
    from repro.models import moe as MOE
    from repro.utils.params import ParamBuilder

    cfg = dataclasses.replace(
        get_config("kimi-k2-1t-a32b").reduced(), dtype="float32",
        d_model=32, num_experts=4, top_k=2, d_ff_expert=16,
        num_shared_experts=0, capacity_factor=32.0)
    b = ParamBuilder(rng, dtype=jnp.float32)
    MOE.init_moe(b, "ffn", cfg)
    params, _ = b.build()
    p = params["ffn"]
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 4, cfg.d_model))
    y, aux = MOE.apply_moe(p, x, cfg)

    # oracle: per token, weighted sum of its top-k experts' FFN outputs
    xf = x.reshape(-1, cfg.d_model)
    logits = xf @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    top_w, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    y_ref = np.zeros_like(np.asarray(xf))
    for t in range(xf.shape[0]):
        for j in range(cfg.top_k):
            e = int(top_i[t, j])
            h = np.asarray(xf[t] @ p["w_in"][e])
            u, g = np.split(h, 2)
            h = u * np.asarray(jax.nn.silu(g))
            y_ref[t] += float(top_w[t, j]) * (h @ np.asarray(p["w_out"][e]))
    np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)), y_ref,
                               rtol=2e-5, atol=2e-5)


def test_int8_kv_cache_decode_close_to_fp(rng):
    """Quantized KV cache: identical argmax, small TV distance vs fp decode."""
    cfg = dataclasses.replace(get_config("yi-9b").reduced(), dtype="float32")
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    m, m8 = Model(cfg), Model(cfg8)
    params, _ = m.init(rng)
    tokens = jax.random.randint(jax.random.fold_in(rng, 1), (2, 12), 0,
                                cfg.vocab_size)
    c, _ = m.init_cache(2, 16)
    c8, specs8 = m8.init_cache(2, 16)
    assert c8["k"].dtype == jnp.int8 and "k_scale" in c8
    s1, s2 = jax.jit(m.decode_step), jax.jit(m8.decode_step)
    for t in range(12):
        l1, c = s1(params, tokens[:, t], c)
        l2, c8 = s2(params, tokens[:, t], c8)
    assert bool((jnp.argmax(l1, -1) == jnp.argmax(l2, -1)).all())
    tv = float(0.5 * jnp.abs(jax.nn.softmax(l1) - jax.nn.softmax(l2)).sum(-1).max())
    assert tv < 0.05, tv


# the configurations whose full-sequence pass fills the decode cache: dense
# and MoE decoders with a plain k/v or MLA latent cache, and Mamba-2 (alone
# or beside attention)
PREFILL_ARCHS = ("chatglm3-6b", "deepseek-v2-236b", "granite-4.0-h-small",
                 "kimi-k2-1t-a32b", "mamba2-130m", "qwen2.5-3b", "qwen2-7b", "yi-9b")


@pytest.mark.parametrize("arch,prompt_len,impl", [
    *((a, 16, None) for a in PREFILL_ARCHS),
    ("qwen2.5-3b", 130, None),                 # a prompt not a multiple of 128
    ("qwen2.5-3b", 130, "pallas_interpret"),   # the flash kernel's ragged tail
])
def test_prefill_then_decode_matches_stepped_decode(arch, prompt_len, impl, rng):
    """``prefill`` and K decode steps give the logits and greedy tokens of
    P - 1 teacher-forced steps and K + 1 more; its cache is the stepped
    cache: k/v and latents in slots [0, P) and zeros after them, recurrent
    states and conv windows whole, with ``pos`` = P."""
    from repro.kernels import ops

    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    model = Model(cfg)
    assert model.can_prefill
    params, _ = model.init(rng)
    B, P, K = 2, prompt_len, 3
    cache_len = P + K + 1
    tokens = jax.random.randint(jax.random.fold_in(rng, 7), (B, P), 0, cfg.vocab_size)
    ops.set_default_impl(impl)
    try:
        logits, cache = jax.jit(model.prefill, static_argnums=2)(params, tokens, cache_len)
    finally:
        ops.set_default_impl(None)
    step = jax.jit(model.decode_step)
    ref, _ = model.init_cache(B, cache_len)
    for t in range(P):
        ref_logits, ref = step(params, tokens[:, t], ref)

    _, axes = model.init_cache(B, cache_len, abstract=True)
    assert int(cache["pos"]) == P and set(cache) == set(ref)
    for name in set(cache) - {"pos", "moe_counts"}:
        assert cache[name].shape == ref[name].shape and cache[name].dtype == ref[name].dtype
        if "kv_seq" not in axes[name]:           # state of the whole prompt
            np.testing.assert_allclose(np.asarray(cache[name]), np.asarray(ref[name]),
                                       rtol=1e-5, atol=1e-5)
            continue
        np.testing.assert_allclose(np.asarray(cache[name][..., :P, :]),
                                   np.asarray(ref[name][..., :P, :]), rtol=1e-5, atol=1e-5)
        assert not np.asarray(cache[name][..., P:, :]).any()
    V = cfg.vocab_size
    for _ in range(K + 1):
        np.testing.assert_allclose(np.asarray(logits[:, :V]), np.asarray(ref_logits[:, :V]),
                                   rtol=1e-4, atol=1e-4)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(jnp.argmax(ref_logits, -1)))
        logits, cache = step(params, tok, cache)
        ref_logits, ref = step(params, tok, ref)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_can_prefill_is_dense_with_a_plain_kv_cache(arch):
    cfg = get_config(arch)
    assert Model(cfg).can_prefill == (arch in PREFILL_ARCHS)
    # an int8 k/v cache has another layout; Mamba-2 and hybrid caches hold
    # no quantized k/v, so the setting leaves them as they are
    int8 = Model(dataclasses.replace(cfg, kv_cache_dtype="int8"))
    assert int8.can_prefill == (arch in PREFILL_ARCHS and cfg.family in ("ssm", "hybrid"))
    for model in (Model(cfg), int8):
        if not model.can_prefill:
            with pytest.raises(ValueError):
                model.prefill(None, jnp.zeros((1, 4), jnp.int32), 8)


def test_prefill_blocks_follow_the_expanded_attention():
    """The qwen2.5-3b cells' prompts (32 x 128, 32 x 1024) go through in one
    block; DeepSeek-V2's 128 x 1024, whose q, k and v expand to 128 heads
    of 192 + 192 + 128 (134 MB a sequence), in blocks of 8 sequences."""
    qwen = Model(get_config("qwen2.5-3b"))
    assert qwen.prefill_rows(32, 128) == qwen.prefill_rows(32, 1024) == 32
    deepseek = Model(get_config("deepseek-v2-236b"))
    assert deepseek.prefill_rows(128, 1024) == 8
    assert deepseek.prefill_rows(12, 1024) == 6           # the most that divides the batch
