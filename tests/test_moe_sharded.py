"""Sharded-MoE equivalence: expert-parallel shard_map paths vs local math.

Runs in a CPU-pinned subprocess with 8 fake devices (XLA_FLAGS must precede
jax init, which pytest's process has already done), asserting:
  - standard expert-parallel apply_moe  == local (no-mesh) apply_moe
  - weight-resident 2D apply_moe_2d     == local apply_moe
in the drop-free regime (high capacity factor).
"""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import moe as MOE
    from repro.utils.params import ParamBuilder
    from repro.utils.sharding import logical_rules

    cfg = dataclasses.replace(
        get_config("kimi-k2-1t-a32b").reduced(), dtype="float32",
        d_model=64, num_experts=8, top_k=2, d_ff_expert=32,
        num_shared_experts=1, capacity_factor=16.0)
    b = ParamBuilder(jax.random.PRNGKey(0), dtype=jnp.float32)
    MOE.init_moe(b, "ffn", cfg)
    params, _ = b.build()
    p = params["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model), jnp.float32)

    y_local, aux_local = MOE.apply_moe(p, x, cfg)          # no mesh: local path

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with logical_rules(mesh):
        y_ep, aux_ep = jax.jit(lambda p, x: MOE.apply_moe(p, x, cfg))(p, x)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_local),
                               rtol=2e-5, atol=2e-5)
    print("expert-parallel == local OK")

    with logical_rules(mesh, {"fsdp": ("data",)}):
        y_2d, aux_2d = jax.jit(
            lambda p, x: MOE.apply_moe(p, x, cfg, impl="2d"))(p, x)
    np.testing.assert_allclose(np.asarray(y_2d), np.asarray(y_local),
                               rtol=2e-5, atol=2e-5)
    print("weight-resident 2D == local OK")
""")


@pytest.mark.timeout(900)
@pytest.mark.slow
def test_sharded_moe_paths_match_local():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"   # 8 fake host devices, never an accelerator
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=860)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "expert-parallel == local OK" in proc.stdout
    assert "weight-resident 2D == local OK" in proc.stdout


SMBLOCK_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.utils.sharding import logical_rules

    cfg = dataclasses.replace(get_config("qwen2-7b").reduced(), dtype="float32",
                              num_heads=4, num_kv_heads=2, head_dim=32,
                              d_model=128, d_ff=256)
    m = Model(cfg)
    params, _ = m.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                          cfg.vocab_size)}
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

    with logical_rules(mesh, {"seq": ("model",)}):
        ref_logits, _, _ = jax.jit(
            lambda p, b: m.forward(p, b, mode="train"))(params, batch)
        m.block_impl = "shardmap"
        sm_logits, _, _ = jax.jit(
            lambda p, b: m.forward(p, b, mode="train"))(params, batch)
    np.testing.assert_allclose(np.asarray(sm_logits), np.asarray(ref_logits),
                               rtol=5e-4, atol=5e-4)
    print("shardmap block == gspmd block OK")

    # gradients flow through the explicit collectives (loss consumes 33
    # tokens -> 32 input positions, divisible by the model axis)
    gbatch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0,
                                           cfg.vocab_size)}
    with logical_rules(mesh, {"seq": ("model",)}):
        g = jax.jit(jax.grad(
            lambda p: m.loss_fn(p, gbatch, remat=False)[0]))(params)
    gn = sum(float(jnp.sum(jnp.square(x.astype(jnp.float32))))
             for x in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0
    print("shardmap grads OK")
""")


@pytest.mark.timeout(900)
@pytest.mark.slow
def test_shardmap_dense_block_matches_gspmd():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"   # 8 fake host devices, never an accelerator
    proc = subprocess.run([sys.executable, "-c", SMBLOCK_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=860)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "shardmap block == gspmd block OK" in proc.stdout
    assert "shardmap grads OK" in proc.stdout
