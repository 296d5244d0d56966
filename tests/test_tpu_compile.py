"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed even where no chip is,
lowers each kernel at the widths of the model that uses it for one chip of
a described ``v5e:2x2`` topology. This catches what interpret mode cannot:
blocks that break the (8, 128) tiling rule and kernels that need more
scoped VMEM than the chip has.

The compiles run in one CPU-pinned child process (this file run as a
script), started by a module fixture, so every xdist worker collects the
same tests and no pytest process loads the TPU library. That library
installs a failure-signal handler which prints a stack trace to the shared
terminal when the process is terminated; in the child its output is
captured.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest


def _flash(window):
    # qwen2.5-3b prefill: 16 query heads, 2 KV heads, head_dim 128, 2048 tokens
    from repro.kernels import ops

    def fn(q, k, v):
        return ops.attention(q, k, v, causal=True, window=window, impl="pallas")
    return fn, [((1, 16, 2048, 128), jnp.bfloat16),
                ((1, 2, 2048, 128), jnp.bfloat16),
                ((1, 2, 2048, 128), jnp.bfloat16)]


def _flash_mla():
    # DeepSeek-V2 prefill block: 8 sequences of 1024, 128 heads, q.k 192, v 128
    from repro.kernels import ops

    def fn(q, k, v):
        return ops.attention(q, k, v, causal=True, impl="pallas")
    return fn, [((8, 128, 1024, 192), jnp.bfloat16),
                ((8, 128, 1024, 192), jnp.bfloat16),
                ((8, 128, 1024, 128), jnp.bfloat16)]


def _ssd():
    # mamba2-130m: d_inner 1536 = 24 heads x 64, state 128, one group, chunk 256
    from repro.kernels import ops

    def fn(*t):
        return ops.ssd(*t, chunk=256, impl="pallas")
    b, length, h, p, g, n = 1, 2048, 24, 64, 1, 128
    return fn, [((b, length, h, p), jnp.bfloat16), ((b, length, h), jnp.float32),
                ((h,), jnp.float32), ((b, length, g, n), jnp.bfloat16),
                ((b, length, g, n), jnp.bfloat16)]


def _ssd_granite():
    # granite-4.0-h-small prefill block: prefill_rows(32, 8192) sequences of
    # 8192, d_inner 8192 = 128 heads x 64, state 128, one group, chunk 256
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models.model import Model

    def fn(*t):
        return ops.ssd(*t, chunk=256, impl="pallas")
    b = Model(get_config("granite-4.0-h-small")).prefill_rows(32, 8192)
    length, h, p, g, n = 8192, 128, 64, 1, 128
    return fn, [((b, length, h, p), jnp.bfloat16), ((b, length, h), jnp.float32),
                ((h,), jnp.float32), ((b, length, g, n), jnp.bfloat16),
                ((b, length, g, n), jnp.bfloat16)]


def _rglru():
    # recurrentgemma-9b: LRU width = d_model = 4096, f32 recurrence, chunk 256
    from repro.kernels import ops

    def fn(a, b):
        return ops.rglru(a, b, chunk=256, impl="pallas")
    return fn, [((1, 2048, 4096), jnp.float32)] * 2


CASES = {
    "flash_causal": lambda: _flash(0),
    "flash_window": lambda: _flash(512),
    "flash_mla": _flash_mla,
    "ssd_mamba2_130m": _ssd,
    "ssd_granite_prefill": _ssd_granite,
    "rglru_w4096": _rglru,
}


def _compile_all():
    """Child entry point: print one JSON object, either ``{"skip": reason}``
    or, for each case, whether its compiled text holds a
    ``tpu_custom_call`` (or the error that stopped the compile)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        print(json.dumps({"skip": f"no v5e:2x2 topology can be described here: {e}"}))
        return
    one_chip = SingleDeviceSharding(topo.devices[0])
    results = {}
    for case in sorted(CASES):
        fn, shapes = CASES[case]()
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
        try:
            compiled = jax.jit(fn).lower(*args).compile()
            results[case] = {"custom_call": "tpu_custom_call" in compiled.as_text()}
        except Exception as e:  # noqa: BLE001 - reported per case by the test
            results[case] = {"error": f"{type(e).__name__}: {e}"[-2000:]}
    print(json.dumps(results))


@pytest.fixture(scope="module")
def compiled_cases():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    env["JAX_PLATFORMS"] = "cpu"        # describes a chip, never takes one
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, __file__], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    if "skip" in out:
        pytest.skip(out["skip"])
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, compiled_cases):
    result = compiled_cases[case]
    assert "error" not in result, result["error"]
    assert result["custom_call"]


if __name__ == "__main__":
    _compile_all()
