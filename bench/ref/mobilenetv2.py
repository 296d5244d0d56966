"""Plain MobileNetV2 in ``jax.numpy``: the reference the partitioned path
is compared with.

Written from the published inverted-residual schedule (Sandler et al.,
CVPR 2018, Table 2), with the paper's TensorFlow padding ("SAME": a
stride-2 3x3 convolution on an even input pads one row and column after,
none before), batch norm from its running statistics and ReLU6. It
imports nothing of the program. Float32 at highest precision; with
``precision="high"`` every convolution and matrix product takes three
bfloat16 passes instead (the operands split into a bfloat16 head and
tail, the tail-by-tail product dropped), as XLA's ``high`` precision
computes them on a TPU: the control, one step below the configuration's
float32 at ``highest``.

Parameters are a list of dicts in forward order, one per convolution
(``w``: HWIO), batch norm (``scale``, ``bias``, ``mean``, ``var``) and the
classifier (``w``, ``b``), made by :func:`init_params` from a key.
"""

from __future__ import annotations

import functools
import json
from typing import List

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def layers(config: dict) -> List[tuple]:
    """The parameterised layers in forward order: ``("conv", k, cin,
    cout, stride, groups)``, ``("bn", c)`` and ``("linear", cin, cout)``."""
    out = []
    cin = config["stem_channels"]
    out += [("conv", 3, config["in_channels"], cin, 2, 1), ("bn", cin)]
    for t, c, n, s in config["inverted_residual_setting"]:
        for i in range(n):
            stride, hidden = (s if i == 0 else 1), cin * t
            if t != 1:
                out += [("conv", 1, cin, hidden, 1, 1), ("bn", hidden)]
            out += [("conv", 3, hidden, hidden, stride, hidden), ("bn", hidden),
                    ("conv", 1, hidden, c, 1, 1), ("bn", c)]
            cin = c
    last = config["last_channels"]
    out += [("conv", 1, cin, last, 1, 1), ("bn", last),
            ("linear", last, config["num_classes"])]
    return out


def init_params(config: dict, key) -> List[dict]:
    """Seeded parameters on the device, in one jitted call.

    Convolution and classifier weights are normal / sqrt(fan_in); each
    batch norm gets a scale 1 + 0.1 N and bias 0.1 N, and as its running
    mean and variance the statistics of its input over a calibration
    batch of 8 seeded images, as a trained network's batch norms hold
    them. Without them the random network's logits hardly depend on its
    input, and a comparison of logits could not tell two images apart.
    """
    return _init(json.dumps(config, sort_keys=True))(key)


@functools.lru_cache(maxsize=None)
def _init(config_json: str):
    config = json.loads(config_json)
    spec = layers(config)
    apply = _model(config)

    def build(key):
        out = []
        for i, layer in enumerate(spec):
            ks = jax.random.split(jax.random.fold_in(key, i), 2)
            if layer[0] == "conv":
                _, k, cin, cout, _, groups = layer
                fan = k * k * cin // groups
                w = jax.random.normal(ks[0], (k, k, cin // groups, cout)) / jnp.sqrt(fan)
                out.append({"w": w})
            elif layer[0] == "bn":
                c = layer[1]
                out.append({"scale": 1.0 + 0.1 * jax.random.normal(ks[0], (c,)),
                            "bias": 0.1 * jax.random.normal(ks[1], (c,)),
                            "mean": jnp.zeros((c,)), "var": jnp.ones((c,))})
            else:
                _, cin, cout = layer
                out.append({"w": jax.random.normal(ks[0], (cin, cout)) / jnp.sqrt(cin),
                            "b": 0.01 * jax.random.normal(ks[1], (cout,))})
        size, ch = config["image_size"], config["in_channels"]
        images = jax.random.normal(jax.random.fold_in(key, len(spec)), (8, size, size, ch))
        _, stats = apply(out, images, "highest", True)
        for i, (mean, var) in stats.items():
            out[i] = dict(out[i], mean=mean, var=var)
        return out
    return jax.jit(build)


def forward(config: dict, params: List[dict], images, precision: str = "highest"):
    """Logits ``(batch, num_classes)`` of NHWC ``images``."""
    return _forward(json.dumps(config, sort_keys=True), precision)(params, images)


@functools.lru_cache(maxsize=None)
def _forward(config_json: str, precision: str):
    apply = _model(json.loads(config_json))
    return jax.jit(lambda params, images: apply(params, images, precision, False)[0])


def _passes(op, x, w, precision):
    """``op(x, w)`` in float32 at highest precision, or in three bfloat16
    passes for ``"high"``."""
    if precision == "highest":
        return op(x, w)
    def split(a):        # reduce_precision: XLA may drop a pair of converts
        head = lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        return head, lax.reduce_precision(a - head, exponent_bits=8, mantissa_bits=7)
    (xh, xt), (wh, wt) = split(x), split(w)
    return op(xh, wh) + op(xh, wt) + op(xt, wh)


def _model(config: dict):
    """``apply(params, images, precision, calibrate) -> (logits, stats)``; with
    ``calibrate`` each batch norm normalises by its input's own statistics
    and returns them by layer index."""
    spec = layers(config)
    eps = config["bn_eps"]

    def apply(params, images, precision, calibrate):
        stats = {}
        it = iter(enumerate(zip(spec, params)))

        def conv(x, p, k, stride, groups):
            pads = []
            for size in x.shape[1:3]:
                out = -(-size // stride)
                total = max((out - 1) * stride + k - size, 0)
                pads.append((total // 2, total - total // 2))
            return _passes(lambda a, b: lax.conv_general_dilated(
                a, b, (stride, stride), pads, feature_group_count=groups,
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST),
                x, p["w"], precision)

        def bn(x, i, p):
            p = dict(p)
            if calibrate:
                p["mean"], p["var"] = x.mean((0, 1, 2)), x.var((0, 1, 2))
                stats[i] = (p["mean"], p["var"])
            return (x - p["mean"]) * lax.rsqrt(p["var"] + eps) * p["scale"] + p["bias"]

        def conv_bn(x):
            (_, (layer, pc)), (i, (_, pb)) = next(it), next(it)
            _, k, _, _, stride, groups = layer
            return bn(conv(x, pc, k, stride, groups), i, pb)

        def relu6(x):
            return jnp.clip(x, 0, 6)

        x = relu6(conv_bn(jnp.asarray(images, jnp.float32)))
        cin = config["stem_channels"]
        for t, c, n, s in config["inverted_residual_setting"]:
            for i in range(n):
                stride, block_in = (s if i == 0 else 1), x
                if t != 1:
                    x = relu6(conv_bn(x))
                x = relu6(conv_bn(x))
                x = conv_bn(x)
                if stride == 1 and cin == c:
                    x = x + block_in
                cin = c
        x = relu6(conv_bn(x))
        x = x.mean(axis=(1, 2))
        _, (_, p) = next(it)
        y = _passes(lambda a, b: jnp.matmul(a, b, precision=HIGHEST), x, p["w"], precision)
        return y + p["b"], stats

    return apply
