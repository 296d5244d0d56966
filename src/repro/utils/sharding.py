"""Logical-axis sharding utilities.

Model code annotates arrays with *logical* axis names ("batch", "embed",
"heads", ...). A thread-global :class:`LogicalRules` maps logical names to
physical mesh axes. When no rules are active every annotation is a no-op, so
the same model code runs on a single CPU device (smoke tests) and on the
production mesh (dry-run / deployment) unchanged.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LogicalAxis = Optional[str]
Axes = Tuple[LogicalAxis, ...]

_state = threading.local()


# Default logical -> mesh-axis rules for the production meshes.  A logical
# name may map to a tuple of mesh axes (e.g. batch sharded over pod+data).
DEFAULT_RULES: Mapping[str, Union[str, Tuple[str, ...], None]] = {
    "batch": ("data",),
    "seq": None,
    "kv_seq": None,          # overridden to ("model",) for seq-sharded decode caches
    "embed": None,
    "heads": ("model",),
    "kv_heads": None,        # GQA kv heads are replicated (kv < model axis size)
    "head_dim": None,
    "ff": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "expert_cap": None,
    "layers": None,
    "fsdp": None,            # set to ("data",) to enable FSDP weight sharding
    "state": None,
    "conv": None,
    "frames": None,
    "img": None,
}


class LogicalRules:
    """Mapping of logical axis names to mesh axis names, bound to a mesh."""

    def __init__(self, mesh: Mesh, rules: Mapping[str, Any]):
        self.mesh = mesh
        self.rules = dict(rules)

    def to_spec(self, axes: Sequence[LogicalAxis]) -> P:
        parts = []
        used: set = set()
        for ax in axes:
            if ax is None:
                parts.append(None)
                continue
            phys = self.rules.get(ax, None)
            if phys is None:
                parts.append(None)
                continue
            if isinstance(phys, str):
                phys = (phys,)
            # drop mesh axes not present in this mesh or already used
            phys = tuple(p for p in phys if p in self.mesh.axis_names and p not in used)
            used.update(phys)
            if not phys:
                parts.append(None)
            elif len(phys) == 1:
                parts.append(phys[0])
            else:
                parts.append(phys)
        return P(*parts)

    def sharding(self, axes: Sequence[LogicalAxis]) -> NamedSharding:
        return NamedSharding(self.mesh, self.to_spec(axes))


def current_rules() -> Optional[LogicalRules]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def logical_rules(mesh: Mesh, overrides: Optional[Mapping[str, Any]] = None):
    """Activate logical sharding rules (and the mesh) for a code region."""
    rules = dict(DEFAULT_RULES)
    if "pod" in mesh.axis_names:
        rules["batch"] = ("pod", "data")
    if overrides:
        rules.update(overrides)
    prev = getattr(_state, "rules", None)
    _state.rules = LogicalRules(mesh, rules)
    try:
        with mesh:
            yield _state.rules
    finally:
        _state.rules = prev


def shard(x: jax.Array, *axes: LogicalAxis) -> jax.Array:
    """Annotate ``x`` with logical axes; no-op when no rules are active."""
    rules = current_rules()
    if rules is None:
        return x
    assert x.ndim == len(axes), f"rank {x.ndim} vs axes {axes}"
    return jax.lax.with_sharding_constraint(x, rules.sharding(axes))


def spec_tree(axes_tree: Any) -> Any:
    """Convert a pytree of logical-axes tuples into PartitionSpecs."""
    rules = current_rules()

    def cvt(axes):
        if rules is None:
            return P()
        return rules.to_spec(axes)

    return jax.tree.map(cvt, axes_tree, is_leaf=lambda a: isinstance(a, tuple))


def is_axes_leaf(a: Any) -> bool:
    return isinstance(a, tuple) and all(x is None or isinstance(x, str) for x in a)


def safe_sharding_tree(args_tree: Any, axes_tree: Any) -> Any:
    """NamedShardings for jit in_shardings, dropping any mesh axis whose size
    does not divide the corresponding array dimension (jit requires exact
    divisibility for input shardings, unlike internal constraints)."""
    rules = current_rules()
    assert rules is not None
    mesh = rules.mesh

    def build(arg, axes):
        spec = rules.to_spec(axes)
        parts = []
        for dim, entry in zip(arg.shape, spec):
            if entry is None:
                parts.append(None)
                continue
            names = (entry,) if isinstance(entry, str) else tuple(entry)
            keep = []
            size = 1
            for nm in names:
                s = mesh.shape[nm]
                if dim % (size * s) == 0:
                    keep.append(nm)
                    size *= s
            parts.append(None if not keep
                         else (keep[0] if len(keep) == 1 else tuple(keep)))
        return NamedSharding(mesh, P(*parts))

    return jax.tree.map(build, args_tree, axes_tree,
                        is_leaf=lambda a: is_axes_leaf(a))


def sharding_tree(axes_tree: Any) -> Any:
    """Convert a pytree of logical-axes tuples into NamedShardings."""
    rules = current_rules()
    assert rules is not None, "sharding_tree requires active logical_rules"
    return jax.tree.map(
        lambda axes: rules.sharding(axes),
        axes_tree,
        is_leaf=lambda a: isinstance(a, tuple),
    )
