"""Executable MobileNetV2 in JAX — the paper's evaluation model.

Structured as an explicit *leaf-layer list* (the same 141 leaves the graph in
``models.graph.mobilenetv2_graph`` describes) so AMP4EC partitions — which
are contiguous leaf ranges — can be executed on different simulated edge
nodes, each as one compiled program over its leaves (``run_range``), and
partitioned output can be asserted identical to the monolithic forward.

Residual adds are attached to the *last* leaf of each inverted-residual
block (the projection BN), mirroring how layer-wise partial inference treats
PyTorch leaf modules: the residual tensor rides along with the activation
between partitions.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import mobilenetv2 as C
from repro.utils import obs


@dataclass
class Leaf:
    name: str
    kind: str
    apply: Callable                      # (params, x, residual) -> (x, residual)
    params: Dict[str, jax.Array]
    # residual bookkeeping
    save_residual: bool = False          # stash x before this leaf
    add_residual: bool = False           # add stash after this leaf


def _conv2d(params, x, stride, groups):
    return jax.lax.conv_general_dilated(
        x, params["w"], window_strides=(stride, stride),
        padding="SAME" if params["w"].shape[0] > 1 else "VALID",
        feature_group_count=groups,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _make_conv(rng, name, cin, cout, k, stride, groups=1) -> Leaf:
    fan = k * k * cin // groups
    w = jax.random.normal(rng, (k, k, cin // groups, cout), jnp.float32) / np.sqrt(fan)
    def apply(p, x, res):
        return _conv2d(p, x, stride, groups), res
    return Leaf(name, "Conv2d", apply, {"w": w})


def _make_bn(rng, name, c) -> Leaf:
    p = {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,)),
         "mean": jnp.zeros((c,)), "var": jnp.ones((c,))}
    def apply(pp, x, res):
        inv = jax.lax.rsqrt(pp["var"] + 1e-5)
        return (x - pp["mean"]) * inv * pp["scale"] + pp["bias"], res
    return Leaf(name, "BatchNorm2d", apply, p)


def _make_relu6(name) -> Leaf:
    def apply(pp, x, res):
        return jnp.clip(x, 0.0, 6.0), res
    return Leaf(name, "ReLU6", apply, {})


def build_mobilenetv2(rng: Optional[jax.Array] = None) -> List[Leaf]:
    """Return the ordered 141-leaf layer list with initialized params."""
    rng = jax.random.PRNGKey(0) if rng is None else rng
    ctr = iter(range(10_000))

    def nxt():
        return jax.random.fold_in(rng, next(ctr))

    leaves: List[Leaf] = []
    # stem
    leaves += [_make_conv(nxt(), "features.0.0", 3, 32, 3, 2),
               _make_bn(nxt(), "features.0.1", 32),
               _make_relu6("features.0.2")]
    cin = 32
    idx = 1
    for t, c, n, s in C.INVERTED_RESIDUAL_SETTING:
        for i in range(n):
            stride = s if i == 0 else 1
            hidden = cin * t
            use_res = stride == 1 and cin == c
            pre = f"features.{idx}"
            first_of_block = len(leaves)
            if t != 1:
                leaves += [_make_conv(nxt(), f"{pre}.pw", cin, hidden, 1, 1),
                           _make_bn(nxt(), f"{pre}.pw_bn", hidden),
                           _make_relu6(f"{pre}.pw_relu")]
            leaves += [_make_conv(nxt(), f"{pre}.dw", hidden, hidden, 3, stride, groups=hidden),
                       _make_bn(nxt(), f"{pre}.dw_bn", hidden),
                       _make_relu6(f"{pre}.dw_relu"),
                       _make_conv(nxt(), f"{pre}.proj", hidden, c, 1, 1),
                       _make_bn(nxt(), f"{pre}.proj_bn", c)]
            if use_res:
                leaves[first_of_block].save_residual = True
                leaves[-1].add_residual = True
            cin = c
            idx += 1
    leaves += [_make_conv(nxt(), "features.18.0", cin, C.LAST_CHANNELS, 1, 1),
               _make_bn(nxt(), "features.18.1", C.LAST_CHANNELS),
               _make_relu6("features.18.2")]

    # classifier (global pool folded into Dropout leaf, mirroring torch's
    # functional pooling between features and classifier)
    def drop_apply(pp, x, res):
        if x.ndim == 4:
            x = x.mean(axis=(1, 2))
        return x, res
    leaves.append(Leaf("classifier.0", "Dropout", drop_apply, {}))
    w = jax.random.normal(nxt(), (C.LAST_CHANNELS, C.NUM_CLASSES), jnp.float32) / np.sqrt(C.LAST_CHANNELS)
    b = jnp.zeros((C.NUM_CLASSES,))
    def lin_apply(pp, x, res):
        return x @ pp["w"] + pp["b"], res
    leaves.append(Leaf("classifier.1", "Linear", lin_apply, {"w": w, "b": b}))
    assert len(leaves) == 141, f"expected 141 leaves, got {len(leaves)}"
    return leaves


_traced = 0                          # stage programs traced in this process


@functools.lru_cache(maxsize=256)
def _stage_program(steps: Tuple[Tuple[Callable, bool, bool], ...]) -> Callable:
    """One jitted program per stage, keyed by the stage's leaf functions and
    residual flags (the callables themselves: a leaf rebuilt with another
    ``apply`` gets another program). Weights are arguments, never constants,
    so leaves rebuilt with other ``params`` reuse the program; shapes,
    dtypes, devices and the matmul precision are ``jax.jit``'s own key."""
    def stage(params, x, residual):
        global _traced
        _traced += 1
        for (apply, save_residual, add_residual), p in zip(steps, params):
            if save_residual:
                residual = x
            x, residual = apply(p, x, residual)
            if add_residual:
                x = x + residual
                residual = None
        return x, residual

    return jax.jit(stage)


def run_range(leaves: List[Leaf], lo: int, hi: int, x: jax.Array,
              residual: Optional[jax.Array] = None):
    """Execute leaves [lo, hi) — one AMP4EC partition — as one compiled
    program. Returns (x, residual).

    While ``obs`` records, an ``amp4ec.stage_program`` span (``lo``, ``hi``)
    carries ``built``: the programs traced during the call (0 on a reused
    one, 1 on a new stage or a new shape)."""
    stage = leaves[lo:hi]
    program = _stage_program(tuple((leaf.apply, leaf.save_residual, leaf.add_residual)
                                   for leaf in stage))
    with obs.span("amp4ec.stage_program", lo=lo, hi=hi) as sp:
        before = _traced
        out = program([leaf.params for leaf in stage], x, residual)
        sp.set(built=_traced - before)
    return out


def run_full(leaves: List[Leaf], x: jax.Array) -> jax.Array:
    y, _ = run_range(leaves, 0, len(leaves), x)
    return y


def partition_params_bytes(leaves: List[Leaf], lo: int, hi: int) -> int:
    total = 0
    for leaf in leaves[lo:hi]:
        for a in jax.tree.leaves(leaf.params):
            total += a.size * a.dtype.itemsize
    return total
