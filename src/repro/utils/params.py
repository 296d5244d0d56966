"""Parameter construction with paired logical-sharding specs.

``ParamBuilder`` creates initialized arrays while recording, in a parallel
pytree, the logical axes of every parameter.  ``init`` functions therefore
return ``(params, specs)`` with identical structure; the launcher converts
``specs`` into PartitionSpecs/NamedShardings via ``utils.sharding``.

For the 512-device dry-run we never materialize weights: ``abstract=True``
makes every param a ShapeDtypeStruct instead.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class ParamBuilder:
    def __init__(self, rng: Optional[jax.Array], dtype=jnp.bfloat16, abstract: bool = False):
        self._rng = rng
        self.dtype = dtype
        self.abstract = abstract
        self.params: Dict[str, Any] = {}
        self.specs: Dict[str, Any] = {}

    def _next_rng(self) -> jax.Array:
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def param(
        self,
        name: str,
        shape: Tuple[int, ...],
        axes: Tuple[Optional[str], ...],
        init: str = "normal",
        scale: Optional[float] = None,
        dtype=None,
    ):
        assert len(shape) == len(axes), f"{name}: shape {shape} vs axes {axes}"
        assert name not in self.params, f"duplicate param {name}"
        dtype = dtype or self.dtype
        if self.abstract:
            arr = jax.ShapeDtypeStruct(shape, dtype)
        elif init == "zeros":
            arr = jnp.zeros(shape, dtype)
        elif init == "ones":
            arr = jnp.ones(shape, dtype)
        elif init == "normal":
            if scale is None:
                # fan-in scaling over the contracting (second-to-last) axis
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                scale = 1.0 / math.sqrt(max(fan_in, 1))
            arr = (jax.random.normal(self._next_rng(), shape, jnp.float32) * scale).astype(dtype)
        elif init == "embedding":
            arr = (jax.random.normal(self._next_rng(), shape, jnp.float32) * (scale or 0.02)).astype(dtype)
        else:
            raise ValueError(f"unknown init {init!r}")
        self.params[name] = arr
        self.specs[name] = axes
        return arr

    def sub(self, name: str) -> "ParamBuilder":
        child = ParamBuilder(None, self.dtype, self.abstract)
        if not self.abstract:
            child._rng = self._next_rng()
        assert name not in self.params, f"duplicate sub {name}"
        self.params[name] = child.params
        self.specs[name] = child.specs
        return child

    def build(self):
        return self.params, self.specs


_stack_jit = jax.jit(lambda *xs: jnp.stack(xs, axis=0))


def stack_layers(per_layer: list):
    """Stack a list of identical-structure (params, specs) into scanned params.

    Arrays gain a leading layer axis; specs gain a leading "layers" entry.
    The list is consumed: each parameter's per-layer arrays are dropped as
    soon as they are stacked, so the layer list and the stacked copy are
    never both held whole.
    """
    specs = per_layer[0][1]
    treedef = jax.tree.structure(per_layer[0][0])
    flat = [jax.tree.leaves(p) for p, _ in per_layer]
    per_layer.clear()
    stacked = []
    for j in range(len(flat[0])):
        # jitted so the stack writes its output directly (eager jnp.stack
        # copies every input first), and waited on so the inputs are freed
        # before the next parameter's stack is dispatched
        stacked.append(jax.block_until_ready(
            _stack_jit(*[leaves[j] for leaves in flat])))
        for leaves in flat:
            leaves[j] = None
    stacked = jax.tree.unflatten(treedef, stacked)
    specs = jax.tree.map(
        lambda axes: ("layers",) + tuple(axes),
        specs,
        is_leaf=lambda a: isinstance(a, tuple),
    )
    return stacked, specs


def abstract_stack(params, specs, num_layers: int):
    """Add a leading layer axis to abstract params without materializing."""
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((num_layers,) + tuple(s.shape), s.dtype), params
    )
    specs = jax.tree.map(
        lambda axes: ("layers",) + tuple(axes),
        specs,
        is_leaf=lambda a: isinstance(a, tuple),
    )
    return stacked, specs


def count_params(params) -> int:
    leaves = jax.tree.leaves(params)
    return int(sum(int(np.prod(l.shape)) for l in leaves))


def tree_bytes(params) -> int:
    leaves = jax.tree.leaves(params)
    return int(sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves))
