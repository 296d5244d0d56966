"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes and dtypes as required for every kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan


def _qkv(key, b, hq, hkv, s, d, dtype, dv=None):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, dv or d), jnp.float32).astype(dtype)
    return q, k, v


ATTN_SHAPES = [
    # (batch, q heads, kv heads, seq, head dim[, value dim])
    (1, 2, 2, 128, 64),
    (2, 4, 2, 256, 64),    # GQA 2:1
    (1, 8, 1, 256, 128),   # MQA
    (2, 2, 2, 384, 32),    # the whole sequence one block
    (1, 4, 2, 200, 64),    # one block of the whole, not a multiple of 128
    (1, 2, 2, 256, 192, 128),   # MLA (DeepSeek-V2): q.k over 128 + 64, v 128
    # longer than one chosen block: several q and k blocks, pairs skipped
    (1, 2, 1, 1024, 128),  # GQA 2:1, two blocks of 512
    (1, 1, 1, 1024, 192, 128),  # MLA at DeepSeek-V2's prompt
    (1, 2, 1, 1100, 64),   # three blocks, the last ragged
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_vs_ref(shape, causal, window):
    b, hq, hkv, s, d, *dv = shape
    q, k, v = _qkv(jax.random.PRNGKey(hash((shape, causal, window)) % 2**31),
                   b, hq, hkv, s, d, jnp.float32, *dv)
    out_ref = ref.attention_ref(q, k, v, causal=causal, window=window)
    out = flash_attention(q, k, v, causal=causal, window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("block_q,block_k", [
    (128, 256), (256, 128),
    # off the TPU's tiling, for interpret mode only: a k block that ends one
    # past a q block's first row, and a q block whose last row is the first
    # of a k block
    (96, 98), (99, 98),
])
@pytest.mark.parametrize("causal,window", [
    (True, 0), (True, 64), (False, 0),
    (True, 130), (False, 127),   # window edges one off a block boundary
])
def test_flash_attention_unequal_blocks(block_q, block_k, causal, window):
    # the skip's block arithmetic where q and k blocks differ; 685 positions
    # leave every last block ragged, the 98-blocks one short of full
    q, k, v = _qkv(jax.random.PRNGKey(block_q + 3 * window + causal),
                   1, 2, 1, 685, 64, jnp.float32)
    out_ref = ref.attention_ref(q, k, v, causal=causal, window=window)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=block_q, block_k=block_k, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
def test_flash_attention_dtypes(dtype, tol):
    q, k, v = _qkv(jax.random.PRNGKey(7), 2, 4, 2, 256, 64, dtype)
    out_ref = ref.attention_ref(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out_ref, np.float32),
                               rtol=tol, atol=tol)


def test_xla_chunked_attention_matches_ref():
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 2, 2, 4096, 32, jnp.float32)
    out = ops._xla_attention_chunked(q, k, v, causal=True, window=0,
                                     scale=None, q_chunk=1024)
    out_ref = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=2e-5, atol=2e-5)


SSD_SHAPES = [
    # (B, L, H, P, G, N, chunk)
    (1, 128, 2, 32, 1, 16, 32),
    (2, 256, 4, 64, 1, 32, 64),
    (1, 256, 4, 64, 2, 32, 128),   # grouped B/C
    (2, 64, 2, 32, 1, 64, 64),     # single chunk
]


def _ssd_inputs(key, B, L, H, P, G, N):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, L, H, P), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H))) * 0.1
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    bm = jax.random.normal(ks[3], (B, L, G, N)) * 0.3
    cm = jax.random.normal(ks[4], (B, L, G, N)) * 0.3
    return x, dt, a, bm, cm


@pytest.mark.parametrize("shape", SSD_SHAPES)
def test_ssd_kernel_vs_sequential(shape):
    B, L, H, P, G, N, chunk = shape
    x, dt, a, bm, cm = _ssd_inputs(jax.random.PRNGKey(sum(shape)), B, L, H, P, G, N)
    y_ref, h_ref = ref.ssd_sequential(x, dt, a, bm, cm)
    y, h = ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=2e-4, atol=2e-4)


def test_ssd_chunked_jnp_vs_sequential():
    x, dt, a, bm, cm = _ssd_inputs(jax.random.PRNGKey(11), 2, 256, 4, 64, 1, 32)
    y_ref, h_ref = ref.ssd_sequential(x, dt, a, bm, cm)
    y, h = ref.ssd_chunked_ref(x, dt, a, bm, cm, chunk=64)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=1e-4, atol=1e-4)


def test_ssd_decode_step_matches_scan():
    B, L, H, P, G, N = 2, 8, 2, 16, 1, 8
    x, dt, a, bm, cm = _ssd_inputs(jax.random.PRNGKey(13), B, L, H, P, G, N)
    y_ref, h_ref = ref.ssd_sequential(x, dt, a, bm, cm)
    h = jnp.zeros((B, H, P, N))
    rep = H // G
    for t in range(L):
        y_t, h = ops.ssd_decode_step(x[:, t], dt[:, t], a, bm[:, t], cm[:, t], h)
    np.testing.assert_allclose(np.asarray(y_t), np.asarray(y_ref[:, -1]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=1e-4, atol=1e-4)


def test_decode_attention_matches_ref():
    B, Hq, Hkv, S, D = 2, 4, 2, 64, 32
    key = jax.random.PRNGKey(5)
    q, k, v = _qkv(key, B, Hq, Hkv, S, D, jnp.float32)
    q1 = q[:, :, -1:, :]
    mask = jnp.ones((B, S), bool)
    out = ops.decode_attention(q1, k, v, mask)
    out_ref = ref.attention_ref(q1, k, v, causal=False)  # full-cache attention
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=2e-5, atol=2e-5)


def test_ops_dispatch_modes():
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 2, 2, 128, 32, jnp.float32)
    a = ops.attention(q, k, v, impl="xla")
    b = ops.attention(q, k, v, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError):
        ops.attention(q, k, v, impl="bogus")


RGLRU_SHAPES = [(1, 128, 64, 64), (2, 256, 128, 128), (1, 512, 96, 256),
                (1, 128, 1024, 64)]   # two 512-lane width tiles


@pytest.mark.parametrize("shape", RGLRU_SHAPES)
def test_rglru_kernel_vs_associative_scan(shape):
    B, L, W, chunk = shape
    ka, kb = jax.random.split(jax.random.PRNGKey(sum(shape)))
    a = jax.nn.sigmoid(jax.random.normal(ka, (B, L, W)))  # decay in (0, 1)
    b = jax.random.normal(kb, (B, L, W)) * 0.5
    h_ref = ref.rglru_ref(a, b)
    h = ops.rglru(a, b, chunk=chunk, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=2e-5, atol=2e-5)


def test_rglru_kernel_bf16():
    ka, kb = jax.random.split(jax.random.PRNGKey(3))
    a = jax.nn.sigmoid(jax.random.normal(ka, (1, 128, 64))).astype(jnp.bfloat16)
    b = (jax.random.normal(kb, (1, 128, 64)) * 0.5).astype(jnp.bfloat16)
    h_ref = ref.rglru_ref(a, b)
    h = ops.rglru(a, b, chunk=64, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(h_ref, np.float32),
                               rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4), (jnp.bfloat16, 3e-2)])
def test_ssd_kernel_dtypes(dtype, tol):
    x, dt, a, bm, cm = _ssd_inputs(jax.random.PRNGKey(21), 1, 128, 2, 32, 1, 16)
    x = x.astype(dtype)
    y_ref, h_ref = ref.ssd_sequential(x, dt, a, bm, cm)
    y, h = ssd_scan(x, dt, a, bm, cm, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol)
