#!/usr/bin/env python3
"""Smoke run of the repo's main paths on one TPU chip: ``python3 chip_smoke.py``.

Every phase runs in this one process (no subprocess, no fork: a chip belongs
to the process that opened it) and fails the run if its check fails:

1. kernels: each Pallas kernel (flash attention, SSD scan, RG-LRU scan) at
   the widths of the model that uses it, against its ``kernels/ref.py``
   oracle evaluated at highest matmul precision; the SSD scan also at
   Granite-4.0-H's prefill block (``prefill_rows`` sequences of 8192).
2. partitioned: MobileNetV2 at 224x224x3 / 1000 classes, cut by the DP
   planner over the paper's three-node cluster. A seeded batch is served
   through ``DistributedInference.infer`` with every stage on the chip,
   checked against the monolithic forward on the chip (``verify_numerics``)
   and against a float32 forward on the host CPU. A short simulated stream
   then runs through ``DistributedInference.run`` in the same process.
   The program's recorder (``repro.utils.obs``) is on for the plan and the
   infer calls: the plan's time and each stage's warm time come from the
   ``amp4ec.plan`` and ``amp4ec.stage`` spans.
3. serving: qwen2.5-3b at published widths behind ``ServingEngine`` (the
   ``repro.launch.serve --full`` path): a few seeded requests with real
   greedy decode, served twice; the tokens must agree. The recorder is on
   for a third, warm serve: its time to first token, gap between tokens,
   routing time and share of prompt positions prefilled come from the spans
   (``serving.engine.measured_ms``).
4. prefill: one jitted 1x2048 prefill with the Pallas flash-attention kernel
   and one with the XLA path; their last-position logits must agree.

Prints device kind, first-call (compile) and warm times taken around
``block_until_ready``, errors against each reference with the tolerance
they are held to, and peak device memory. With no TPU it exits non-zero
before any phase. The last line of stdout is the JSON result.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs import mobilenetv2 as MNV2  # noqa: E402
from repro.core import ModelPartitioner, make_paper_cluster  # noqa: E402
from repro.core.pipeline import DistributedInference  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import build_engine  # noqa: E402
from repro.models.graph import mobilenetv2_graph  # noqa: E402
from repro.models.mobilenetv2 import build_mobilenetv2, run_full, run_range  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.models.rglru import lru_width  # noqa: E402
from repro.models.ssm import ssm_dims  # noqa: E402
from repro.serving import Request  # noqa: E402
from repro.serving.engine import measured_counts, measured_ms  # noqa: E402
from repro.utils import obs  # noqa: E402

# Every error below is max|out - reference| / max|reference|.
#: chip vs host float32 MobileNetV2: XLA on the TPU runs f32 convolutions
#: with bf16 passes by default, compounded over 52 conv layers
HOST_REF_TOL = 5e-2
#: Pallas vs XLA prefill: bf16 weights and activations through 36 layers
PREFILL_TOL = 5e-2
#: kernel vs oracle: flash takes bf16 q/k/v; SSD takes f32 operands whose
#: in-kernel matmuls may run bf16 passes; RG-LRU is elementwise f32
KERNEL_TOLS = {"flash": 2e-2, "flash_window": 2e-2, "flash_mla": 2e-2, "ssd": 1e-2,
               "ssd_granite": 1e-2, "rglru": 1e-4}


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def _rel_err(out, reference) -> float:
    out = np.asarray(out, np.float32)
    reference = np.asarray(reference, np.float32)
    _check(out.shape == reference.shape, f"shape {out.shape} vs {reference.shape}")
    _check(bool(np.isfinite(out).all()), "non-finite values in output")
    return float(np.abs(out - reference).max() / np.abs(reference).max())


def _timed(fn, *args):
    """(result, first-call s, warm-call s); the first call includes compile."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, first, time.perf_counter() - t0


@contextlib.contextmanager
def _recording():
    """The program's recorder on for the block; yields the list that
    holds its spans once the block ends."""
    spans = []
    obs.enable()
    try:
        yield spans
    finally:
        obs.disable()
        spans += obs.snapshot()["spans"]


def _peak_gb() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.3f} GB"


# ---------------------------------------------------------------------------
# 1. kernels
# ---------------------------------------------------------------------------

def kernel_shapes(seq: int = 2048) -> dict:
    """Kernel problem sizes at the widths of the configs that use them."""
    q = get_config("qwen2.5-3b")
    ds = get_config("deepseek-v2-236b")
    m = get_config("mamba2-130m")
    _, heads, groups, _ = ssm_dims(m)
    g = get_config("granite-4.0-h-small")
    _, g_heads, g_groups, _ = ssm_dims(g)
    return dict(
        flash=(1, q.num_heads, q.num_kv_heads, seq, q.head_dim_),
        # MLA: every head has its own k and v, q.k over nope + rope widths
        flash_mla=(1, ds.num_heads, ds.num_heads, seq,
                   ds.qk_nope_head_dim + ds.qk_rope_head_dim, ds.v_head_dim),
        ssd=(1, seq, heads, m.ssm_head_dim, groups, m.ssm_state, m.ssm_chunk),
        # the served prompt of the Granite cell, one prefill block of rows
        ssd_granite=(Model(g).prefill_rows(32, 8192), 8192, g_heads, g.ssm_head_dim,
                     g_groups, g.ssm_state, g.ssm_chunk),
        rglru=(1, seq, lru_width(get_config("recurrentgemma-9b")), 256),
    )


def phase_kernels(shapes: dict, impl: str = "pallas", seed: int = 0) -> dict:
    """Run each kernel through ``ops`` with ``impl`` against its oracle."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    errs = {}

    def run(name, fn, oracle, *args):
        out, first, warm = _timed(jax.jit(fn), *args)
        with jax.default_matmul_precision("highest"):
            expect = jax.jit(oracle)(*args)
        outs, expects = (out, expect) if isinstance(out, tuple) else ((out,), (expect,))
        errs[name] = max(_rel_err(o, e) for o, e in zip(outs, expects))
        print(f"kernel {name} {args[0].shape}: first {first:.3f} s, warm "
              f"{warm * 1e3:.3f} ms, err {errs[name]:.3e} (tol {KERNEL_TOLS[name]:g})")
        _check(errs[name] <= KERNEL_TOLS[name], f"kernel {name} off its oracle")

    def attention(name, shape, key, window):
        b, hq, hkv, s, d, *dv = shape
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, hq, s, d), jnp.float32).astype(jnp.bfloat16)
        k = jax.random.normal(kk, (b, hkv, s, d), jnp.float32).astype(jnp.bfloat16)
        v = jax.random.normal(kv, (b, hkv, s, *(dv or [d])), jnp.float32).astype(jnp.bfloat16)
        run(name,
            lambda q, k, v: ops.attention(q, k, v, causal=True, window=window, impl=impl),
            lambda q, k, v: ref.attention_ref(q, k, v, causal=True, window=window),
            q, k, v)

    attention("flash", shapes["flash"], ks[0], 0)
    attention("flash_window", shapes["flash"], ks[0], shapes["flash"][3] // 4)
    attention("flash_mla", shapes["flash_mla"], ks[1], 0)

    for name in ("ssd", "ssd_granite"):
        b, length, h, p, g, n, chunk = shapes[name]
        k3, k4, k5, k6 = (jax.random.fold_in(k, name == "ssd_granite") for k in ks[3:7])
        x = jax.random.normal(k3, (b, length, h, p), jnp.float32) * 0.5
        dt = jax.nn.softplus(jax.random.normal(k4, (b, length, h))) * 0.1
        a = -jnp.exp(jax.random.normal(k5, (h,)) * 0.5)
        bm, cm = jax.random.normal(k6, (2, b, length, g, n)) * 0.3
        run(name, lambda *t, chunk=chunk: ops.ssd(*t, chunk=chunk, impl=impl),
            ref.ssd_sequential, x, dt, a, bm, cm)

    b, length, w, chunk = shapes["rglru"]
    ka, kb = jax.random.split(ks[7])
    a = jax.nn.sigmoid(jax.random.normal(ka, (b, length, w)))
    bb = jax.random.normal(kb, (b, length, w)) * 0.5
    run("rglru", lambda a, b: ops.rglru(a, b, chunk=chunk, impl=impl), ref.rglru_ref,
        a, bb)
    return errs


# ---------------------------------------------------------------------------
# 2. partitioned MobileNetV2
# ---------------------------------------------------------------------------

def phase_partitioned(batch: int = 8, image: int = MNV2.IMAGE_SIZE,
                      stream: int = 24, seed: int = 0) -> dict:
    """Plan, verify and serve MobileNetV2 through the partitioned path."""
    dev = jax.devices()[0]
    leaves = build_mobilenetv2(jax.random.PRNGKey(seed))
    with _recording() as spans:
        d = DistributedInference(
            make_paper_cluster(), ModelPartitioner(mobilenetv2_graph()),
            method="planner", batch=batch,
            executor=lambda lo, hi, x, res: run_range(leaves, lo, hi, x, res))
    plan_ms = sum(s.end - s.start for s in spans if s.name == "amp4ec.plan") * 1e3
    print(f"partitioned: plan {d.plan.sizes} on {d.placement} in {plan_ms:.3f} ms")
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (batch, image, image, 3))
    t0 = time.perf_counter()
    _check(d.verify_numerics(x), "partitioned forward != monolithic forward on chip")
    print(f"partitioned == monolithic on {dev.device_kind} "
          f"(rtol 1e-5, atol 1e-5): {time.perf_counter() - t0:.3f} s incl. compile")

    with _recording() as spans:
        y, first, warm = _timed(d.infer, x)
    last = max(s.span_id for s in spans if s.name == "amp4ec.infer")
    stage_ms = [(s.end - s.start) * 1e3 for s in spans
                if s.name == "amp4ec.stage" and s.root_id == last]
    print("partitioned warm stages (ms): " + ", ".join(f"{ms:.3f}" for ms in stage_ms))
    _check(y.shape == (batch, MNV2.NUM_CLASSES), f"output shape {y.shape}")
    _check(y.devices() == {dev}, f"stages ran on {y.devices()}, not {dev}")
    cpu = jax.devices("cpu")[0]
    host_leaves = [dataclasses.replace(lf, params=jax.device_put(lf.params, cpu))
                   for lf in leaves]
    with jax.default_device(cpu):
        y_host = run_full(host_leaves, jax.device_put(x, cpu))
    err = _rel_err(y, y_host)
    print(f"partitioned infer {tuple(x.shape)}: first {first:.3f} s, warm "
          f"{warm * 1e3:.3f} ms; vs host f32 err {err:.3e} (tol {HOST_REF_TOL:g})")
    _check(err <= HOST_REF_TOL, "partitioned forward off the host f32 reference")

    rep = d.run(stream, concurrency=4)
    _check(rep.done_count == stream, f"stream finished {rep.done_count}/{stream}")
    print(f"partitioned stream (simulated clock): {rep.done_count} requests, "
          f"{rep.throughput_rps:.3f} rps, avg latency {rep.avg_latency_ms:.3f} ms")
    return dict(host_err=err, first_s=first, warm_s=warm, plan_ms=plan_ms,
                stage_ms=stage_ms)


# ---------------------------------------------------------------------------
# 3. serving and 4. prefill
# ---------------------------------------------------------------------------

def phase_serving(cfg, *, requests: int = 4, prompt_len: int = 16,
                  new_tokens: int = 8, seed: int = 0):
    """Serve seeded prompts twice with greedy decode; returns the engine."""
    t0 = time.perf_counter()
    engine = build_engine(cfg, max_batch=requests, seed=seed)
    jax.block_until_ready(engine.params)
    print(f"serving {cfg.name}: {engine.model.param_count(engine.params) / 1e9:.3f} B "
          f"params ({cfg.dtype}), init {time.perf_counter() - t0:.3f} s, "
          f"peak {_peak_gb()}")
    prompts = np.random.default_rng(seed).integers(
        1, cfg.vocab_size, (requests, prompt_len), dtype=np.int32)

    def serve():
        reqs = [Request(i, prompts[i], new_tokens) for i in range(requests)]
        engine.serve(reqs)
        return np.stack([r.output for r in reqs])

    tokens, first, warm = _timed(serve)
    with _recording() as spans:
        again = serve()
    measured = measured_ms({"spans": spans})
    counts = measured_counts({"spans": spans})
    _check(tokens.shape == (requests, new_tokens), f"tokens shape {tokens.shape}")
    _check(bool(((tokens >= 0) & (tokens < cfg.vocab_size)).all()),
           "token outside the vocabulary")
    _check(np.array_equal(tokens, again), "greedy decode differs between serves")
    print(f"serving {requests} x ({prompt_len} prompt + {new_tokens} new): first "
          f"{first:.3f} s, warm {warm:.3f} s, tokens identical across serves, "
          f"peak {_peak_gb()}")
    share = measured.pop("prefill_share")
    print("serving, measured on the host clock (ms): "
          + ", ".join(f"{k} {v:.3f}" for k, v in measured.items())
          + f"; prefill_share {share}")
    if counts["routed_here"] is not None:
        print("serving, MoE counters: " + ", ".join(f"{k} {v}" for k, v in counts.items()))
        _check(counts["dropped"] == 0, "the serving path dropped routed tokens")
    print(f"serving tokens[0]: {tokens[0].tolist()}")
    return engine


def _prefill_jit(model: Model, impl: str):
    """A jitted last-position prefill bound to one kernel implementation.

    ``ops`` reads the default impl at trace time and jit does not key on it,
    so each impl gets its own jitted function that sets it while tracing.
    """
    def prefill(params, tokens):
        ops.set_default_impl(impl)
        try:
            logits, _, _ = model.forward(params, {"tokens": tokens}, mode="prefill")
        finally:
            ops.set_default_impl(None)
        return logits
    return jax.jit(prefill)


def phase_prefill(cfg, params, *, seq: int = 2048, impl: str = "pallas",
                  seed: int = 0) -> float:
    """Prefill with the Pallas kernel vs the XLA path; returns the error."""
    model = Model(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (1, seq), 0, cfg.vocab_size)
    out = {}
    for name in (impl, "xla"):
        logits, first, warm = _timed(_prefill_jit(model, name), params, tokens)
        # the padded vocab tail is masked to -1e30, which would swamp the error
        out[name] = logits[:, :cfg.vocab_size]
        print(f"prefill {cfg.name} 1x{seq} [{name}]: first {first:.3f} s, "
              f"warm {warm * 1e3:.3f} ms")
    err = _rel_err(out[impl], out["xla"])
    print(f"prefill {impl} vs xla last-position logits (max |logit| "
          f"{float(jnp.abs(out['xla']).max()):.3f}): err {err:.3e} "
          f"(tol {PREFILL_TOL:g}), peak {_peak_gb()}")
    _check(err <= PREFILL_TOL, "Pallas prefill off the XLA prefill")
    return err


def main() -> None:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform}: {dev.device_kind}); "
                 "no phase was run")
    cache = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache}")
    phase_kernels(kernel_shapes())
    phase_partitioned()
    engine = phase_serving(get_config("qwen2.5-3b"))
    phase_prefill(engine.cfg, engine.params)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))


if __name__ == "__main__":
    main()
