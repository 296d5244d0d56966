import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
os.environ["JAX_PLATFORMS"] = "cpu"   # the 512 placeholders are host devices

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

MUST be executed as its own process (``python -m repro.launch.dryrun``):
the XLA_FLAGS line above runs before any other import so the 512 placeholder
devices exist before jax locks the device count, and the platform is pinned
to the CPU so a machine with an accelerator still lowers onto them. Nothing
here allocates real buffers — parameters, optimizer state and caches are
ShapeDtypeStructs; ``.compile()`` produces the SPMD executable whose
memory/cost analyses and HLO feed EXPERIMENTS.md §Dry-run/§Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out artifacts/dryrun]
  ... --opt fsdp,remat_none   # perf-iteration variants (§Perf)
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.core.cost_model import TPU_HBM_BW, TPU_ICI_BW, TPU_PEAK_FLOPS
from repro.launch.mesh import make_production_mesh
from repro.models.model import Model
from repro.optim import adamw, cosine_with_warmup
from repro.utils.hlo import collective_bytes, op_histogram
from repro.utils.params import count_params
from repro.utils.sharding import logical_rules, safe_sharding_tree


def active_param_count(cfg, total: int) -> int:
    """Parameters touched per token (MoE discounts inactive experts)."""
    if not cfg.num_experts:
        return total
    per_expert = 3 * cfg.d_model * cfg.d_ff_expert
    n_moe = cfg.num_layers - cfg.first_dense_layers
    inactive = (cfg.num_experts - cfg.top_k) * per_expert * n_moe
    return total - inactive


def model_flops(cfg, shape_name: str, total_params: int) -> float:
    sh = INPUT_SHAPES[shape_name]
    act = active_param_count(cfg, total_params)
    tokens = sh["global_batch"] * (sh["seq_len"] if sh["kind"] != "decode" else 1)
    mult = 6.0 if sh["kind"] == "train" else 2.0
    return mult * act * tokens


def rules_overrides(cfg, shape_name: str, opts) -> Dict[str, Any]:
    ov: Dict[str, Any] = {}
    kind = INPUT_SHAPES[shape_name]["kind"]
    if kind == "decode" and "no_kvseq_shard" not in opts:
        ov["kv_seq"] = ("model",)        # shard decode caches along sequence
    if "seqpar" in opts or "smblock" in opts:
        ov["seq"] = ("model",)           # sequence-parallel residual stream
    if "ep2d" in opts:
        ov["experts"] = ("data", "model")  # 2D expert parallelism (decode)
    if "fsdp" in opts or "zero1" in opts:
        ov["fsdp"] = ("data",)
    return ov


def build_step(model: Model, shape_name: str, opts) -> Dict[str, Any]:
    """Returns dict(fn=..., args=(...), arg_axes=(...)) with abstract args."""
    cfg = model.cfg
    kind = INPUT_SHAPES[shape_name]["kind"]
    sh = INPUT_SHAPES[shape_name]
    params, pspecs = model.init(abstract=True)
    if "fsdp" in opts:
        # ZeRO-style: additionally shard every >=2D param's first unsharded
        # dim over the data axis (weights gathered per layer on use)
        def add_fsdp(axes):
            if len(axes) >= 2 and "fsdp" not in axes:
                for i, a in enumerate(axes):
                    if a is None:
                        return axes[:i] + ("fsdp",) + axes[i + 1:]
            return axes
        pspecs = jax.tree.map(add_fsdp, pspecs,
                              is_leaf=lambda a: isinstance(a, tuple))
    ishapes = model.input_specs(shape_name)
    remat = "remat_none" not in opts

    window = 0
    if shape_name == "long_500k" and cfg.long_context == "sliding":
        window = cfg.window

    if kind == "train":
        opt = adamw(cosine_with_warmup(3e-4, 100, 10_000))
        opt_state = jax.eval_shape(opt.init, params)
        from repro.optim.adamw import opt_state_specs
        ospecs_base = pspecs
        if "zero1" in opts and "fsdp" not in opts:
            # ZeRO-1: shard ONLY the fp32 moments over data; weights stay
            # replicated across data (no per-layer gathers in fwd/bwd)
            def add_fsdp1(axes):
                if len(axes) >= 2 and "fsdp" not in axes:
                    for i, a in enumerate(axes):
                        if a is None:
                            return axes[:i] + ("fsdp",) + axes[i + 1:]
                return axes
            ospecs_base = jax.tree.map(add_fsdp1, pspecs,
                                       is_leaf=lambda a: isinstance(a, tuple))
        ospecs = opt_state_specs(ospecs_base)

        def train_step(p, s, batch):
            def loss_fn(p_):
                total, nll = model.loss_fn(p_, batch, remat=remat)
                return total, nll
            (loss, nll), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
            p2, s2, om = opt.update(grads, s, p)
            return p2, s2, dict(loss=loss, nll=nll, **om)

        batch_axes = {k: ("batch",) + (None,) * (len(v.shape) - 1)
                      for k, v in ishapes.items()}
        return dict(fn=train_step, args=(params, opt_state, ishapes),
                    axes=(pspecs, ospecs, batch_axes))

    if kind == "prefill":
        def prefill_step(p, batch):
            logits, aux, cache = model.forward(p, batch, mode="prefill",
                                               window=window)
            return logits, cache

        batch_axes = {k: ("batch",) + (None,) * (len(v.shape) - 1)
                      for k, v in ishapes.items()}
        return dict(fn=prefill_step, args=(params, ishapes),
                    axes=(pspecs, batch_axes))

    # decode
    B = sh["global_batch"]
    S = sh["seq_len"]
    if cfg.family in ("ssm", "hybrid"):
        cache_len = min(S, cfg.local_window or S) if cfg.family == "hybrid" else 0
        cache_len = cache_len or 1
    elif window:
        cache_len = window
    else:
        cache_len = S
    cache, cspecs = model.init_cache(B, cache_len, abstract=True)
    cspecs["pos"] = ()

    def decode_fn(p, token, cache_):
        return model.decode_step(p, token, cache_, window=window)

    token = ishapes["token"]
    return dict(fn=decode_fn, args=(params, token, cache),
                axes=(pspecs, ("batch",), cspecs))


def depth_variants(cfg):
    """Two shallow full-width configs + unit counts for flop extrapolation.

    XLA's cost_analysis reports while-loop bodies once (not x trip count), so
    the dry-run compiles two UNROLLED shallow variants of the same width and
    extrapolates: total = f(base) + delta_per_unit * (units_full - units_base).
    Returns (cfg_base, cfg_big, units_base, units_big, units_full, note).
    """
    import dataclasses as dc
    f = cfg.family
    if f in ("dense", "ssm"):
        return (dc.replace(cfg, num_layers=2), dc.replace(cfg, num_layers=4),
                2, 4, cfg.num_layers, "")
    if f == "moe":
        fd = cfg.first_dense_layers
        return (dc.replace(cfg, num_layers=fd + 1), dc.replace(cfg, num_layers=fd + 3),
                1, 3, cfg.num_layers - fd, "")
    if f == "hybrid":
        k = len(cfg.block_pattern)
        tail = cfg.num_layers % k
        note = (f"+{tail} tail layers approximated as {tail}/{k} of a super-block"
                if tail else "")
        return (dc.replace(cfg, num_layers=k), dc.replace(cfg, num_layers=2 * k),
                1, 2, cfg.num_layers / k, note)
    if f == "audio":
        return (dc.replace(cfg, num_layers=2, encoder_layers=2),
                dc.replace(cfg, num_layers=4, encoder_layers=4),
                2, 4, cfg.num_layers, "enc+dec layers scale together")
    if f == "vlm":
        e = cfg.cross_attn_every
        return (dc.replace(cfg, num_layers=e), dc.replace(cfg, num_layers=2 * e),
                1, 2, cfg.num_layers / e, "")
    raise ValueError(f)


def _lower_compile(cfg, shape_name, mesh, opts, unroll):
    model = Model(cfg)
    if unroll:
        model.scan_unroll = True
    if "remat_outputs" in opts:
        model.remat_policy = "outputs"
    if "moe2d" in opts:
        model.moe_impl = "2d"
    if "smblock" in opts:
        model.block_impl = "shardmap"
    with logical_rules(mesh, rules_overrides(cfg, shape_name, opts)):
        step = build_step(model, shape_name, opts)
        in_shardings = safe_sharding_tree(step["args"], step["axes"])
        jitted = jax.jit(step["fn"], in_shardings=in_shardings)
        t0 = time.perf_counter()
        lowered = jitted.lower(*step["args"])
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
    return step, compiled, t_lower, t_compile


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               opts=(), accounting: str = "extrapolate",
               verbose: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    if "kv_int8" in opts:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, kv_cache_dtype="int8")
    if shape_name == "long_500k" and cfg.long_context == "skip":
        return dict(arch=arch, shape=shape_name, multi_pod=multi_pod,
                    status="skipped",
                    reason="enc-dec ASR backbone has no 500k decoder context "
                           "(DESIGN.md §Arch-applicability)")
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    record: Dict[str, Any] = dict(arch=arch, shape=shape_name,
                                  multi_pod=multi_pod, chips=chips,
                                  opts=list(opts), accounting=accounting)

    # full-depth rolled compile: memory analysis + proves the config lowers
    step, compiled, t_lower, t_compile = _lower_compile(
        cfg, shape_name, mesh, opts, unroll=False)
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll_total, coll_by_kind, coll_counts = collective_bytes(hlo)
    flops_dev = float(ca.get("flops", 0.0))
    bytes_dev = float(ca.get("bytes accessed", 0.0))

    if accounting == "extrapolate":
        cfg_b, cfg_g, u_b, u_g, u_full, note = depth_variants(cfg)
        _, comp_b, _, _ = _lower_compile(cfg_b, shape_name, mesh, opts, unroll=True)
        _, comp_g, _, _ = _lower_compile(cfg_g, shape_name, mesh, opts, unroll=True)
        f_b = float((comp_b.cost_analysis() or {}).get("flops", 0.0))
        f_g = float((comp_g.cost_analysis() or {}).get("flops", 0.0))
        c_b, kinds_b, _ = collective_bytes(comp_b.as_text())
        c_g, kinds_g, _ = collective_bytes(comp_g.as_text())
        d_units = max(u_g - u_b, 1e-9)
        f_delta = (f_g - f_b) / d_units
        c_delta = (c_g - c_b) / d_units
        flops_dev = f_b + f_delta * (u_full - u_b)
        coll_total = c_b + c_delta * (u_full - u_b)
        coll_by_kind = {
            k: kinds_b.get(k, 0.0)
            + (kinds_g.get(k, 0.0) - kinds_b.get(k, 0.0)) / d_units * (u_full - u_b)
            for k in set(kinds_b) | set(kinds_g)}
        record["extrapolation"] = dict(
            units=(u_b, u_g, u_full), flops=(f_b, f_g),
            coll=(c_b, c_g), note=note,
            flops_rolled_body_once=float(ca.get("flops", 0.0)))

    params_total = count_params(step["args"][0])
    mf = model_flops(cfg, shape_name, params_total)

    compute_s = flops_dev / TPU_PEAK_FLOPS
    memory_s = bytes_dev / TPU_HBM_BW
    coll_s = coll_total / TPU_ICI_BW
    terms = dict(compute_s=compute_s, memory_s=memory_s, collective_s=coll_s)
    dominant = max(terms, key=terms.get)

    record.update(
        status="ok",
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        params_total=params_total,
        params_active=active_param_count(cfg, params_total),
        flops_per_device=flops_dev,
        bytes_per_device=bytes_dev,
        collective_bytes_per_device=coll_total,
        collective_by_kind=coll_by_kind,
        collective_counts=coll_counts,
        hlo_ops=op_histogram(hlo),
        memory=dict(
            argument_bytes=ma.argument_size_in_bytes,
            output_bytes=ma.output_size_in_bytes,
            temp_bytes=ma.temp_size_in_bytes,
            code_bytes=ma.generated_code_size_in_bytes,
            total_gb=round((ma.argument_size_in_bytes + ma.temp_size_in_bytes
                            + ma.output_size_in_bytes) / 2**30, 3),
        ),
        model_flops=mf,
        useful_flops_ratio=round(mf / max(flops_dev * chips, 1.0), 4),
        roofline=dict(**{k: float(v) for k, v in terms.items()},
                      dominant=dominant),
    )
    if verbose:
        m = record["memory"]
        print(f"[{arch} x {shape_name} x {'2x16x16' if multi_pod else '16x16'}"
              f"{' ' + ','.join(opts) if opts else ''}] "
              f"compile {t_compile:.1f}s | mem/dev {m['total_gb']:.2f} GiB | "
              f"flops/dev {flops_dev:.3e} | coll/dev {coll_total:.3e} B | "
              f"terms c={compute_s*1e3:.2f}ms m={memory_s*1e3:.2f}ms "
              f"x={coll_s*1e3:.2f}ms -> {dominant} | "
              f"useful {record['useful_flops_ratio']:.2f}")
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--opt", default="", help="comma-separated perf options")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--continue-on-error", action="store_true")
    ap.add_argument("--accounting", default="extrapolate",
                    choices=["extrapolate", "rolled"],
                    help="rolled = single fast compile (flops count loop "
                         "bodies once); extrapolate = +2 shallow unrolled "
                         "compiles for exact per-layer flop/collective scaling")
    args = ap.parse_args()

    opts = tuple(o for o in args.opt.split(",") if o)
    combos = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for a, s, mp in combos:
        tag = f"{a}__{s}__{'pod2' if mp else 'pod1'}"
        if opts:
            tag += "__" + "-".join(opts)
        try:
            rec = dryrun_one(a, s, multi_pod=mp, opts=opts,
                             accounting=args.accounting)
        except Exception as e:
            failures += 1
            rec = dict(arch=a, shape=s, multi_pod=mp, status="error",
                       error=f"{type(e).__name__}: {e}",
                       traceback=traceback.format_exc()[-2000:])
            print(f"[{tag}] FAILED: {rec['error']}")
            if not args.continue_on_error:
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1)
                raise
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    print(f"done: {len(combos) - failures}/{len(combos)} OK")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
