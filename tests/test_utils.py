"""Utility-layer tests: HLO collective parser, sharding helpers, cache."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.cache import ResultCache, digest
from repro.utils.hlo import collective_bytes, op_histogram
from repro.utils.sharding import (DEFAULT_RULES, LogicalRules, logical_rules,
                                  safe_sharding_tree, shard)


def make_mesh(shape, names):
    return jax.make_mesh(shape, names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))


SAMPLE_HLO = """
ENTRY %main {
  %p0 = bf16[8,128]{1,0} parameter(0)
  %ar = bf16[8,128]{1,0} all-reduce(%p0), replica_groups={}, to_apply=%add
  %ag = f32[64,128]{1,0} all-gather(%p0), dimensions={0}
  %rs = f32[2,128]{1,0} reduce-scatter(%ag), dimensions={0}, to_apply=%add
  %cp = bf16[8,128]{1,0} collective-permute(%ar), source_target_pairs={{0,1}}
  %dot = f32[8,8]{1,0} dot(%ag, %ag)
}
"""


def test_collective_bytes_parser():
    total, by_kind, counts = collective_bytes(SAMPLE_HLO)
    ar = 8 * 128 * 2 * 2.0          # bf16, wire factor 2
    ag = 64 * 128 * 4
    rs = 2 * 128 * 4
    cp = 8 * 128 * 2
    assert by_kind["all-reduce"] == ar
    assert by_kind["all-gather"] == ag
    assert by_kind["reduce-scatter"] == rs
    assert by_kind["collective-permute"] == cp
    assert total == ar + ag + rs + cp
    assert counts == {"all-reduce": 1, "all-gather": 1,
                      "reduce-scatter": 1, "collective-permute": 1}


def test_op_histogram():
    hist = op_histogram(SAMPLE_HLO)
    assert hist["all-reduce"] == 1 and hist["all-gather"] == 1


def _norm(spec):
    """PartitionSpec entries tuple-normalized ('x' -> ('x',))."""
    return tuple((p,) if isinstance(p, str) else p for p in spec)


def test_logical_rules_to_spec():
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = LogicalRules(mesh, DEFAULT_RULES)
    assert _norm(rules.to_spec(("batch", None, "heads"))) == \
        (("data",), None, ("model",))
    # duplicate mesh axes dropped (an axis may shard only one dim)
    assert _norm(rules.to_spec(("heads", "ff"))) == (("model",), None)


def test_shard_noop_without_rules():
    x = jnp.zeros((4, 4))
    assert shard(x, "batch", None) is x


def test_safe_sharding_drops_nondivisible():
    mesh = make_mesh((1, 1), ("data", "model"))
    with logical_rules(mesh):
        arg = jax.ShapeDtypeStruct((5, 8), jnp.float32)   # 5 % 1 == 0 trivially
        sh = safe_sharding_tree((arg,), (("heads", "ff"),))
        assert sh[0].spec == P("model", None) or sh[0].spec == P(None, None) \
            or sh[0].spec == P(("model",), None)


def test_safe_sharding_nondivisible_dim_dropped():
    mesh = make_mesh((1,), ("model",))
    with logical_rules(mesh):
        arg = jax.ShapeDtypeStruct((24, 7), jnp.float32)
        (s,) = safe_sharding_tree((arg,), (("heads", "vocab"),))
        # axis of size 1 always divides; vocab=7 % 1 == 0 too
        assert s.spec is not None


def test_result_cache_lru_and_stats():
    c = ResultCache(capacity=2)
    k1, k2, k3 = ("m", 0, "a"), ("m", 0, "b"), ("m", 0, "c")
    assert c.get(k1) is None
    c.put(k1, 1)
    c.put(k2, 2)
    assert c.get(k1) == 1
    c.put(k3, 3)                      # evicts k2 (LRU)
    assert c.get(k2) is None
    assert c.get(k3) == 3
    s = c.stats()
    assert s["hits"] == 2 and s["misses"] == 2 and s["entries"] == 2


def test_digest_is_content_sensitive():
    import numpy as np
    a = np.arange(8)
    b = np.arange(8)
    c = np.arange(8) + 1
    assert digest(a) == digest(b) != digest(c)
    assert digest(a.reshape(2, 4)) != digest(a)


def test_stack_layers_consumes_list_and_keeps_values():
    from repro.utils.params import ParamBuilder, stack_layers
    per_layer = []
    for i in range(3):
        b = ParamBuilder(jax.random.PRNGKey(i), dtype=jnp.float32)
        b.param("w", (4, 8), (None, "ff"))
        b.sub("ln").param("scale", (8,), (None,), init="ones")
        per_layer.append(b.build())
    expect = jnp.stack([p["w"] for p, _ in per_layer])
    params, specs = stack_layers(per_layer)
    assert per_layer == []            # per-layer arrays released as stacked
    assert (params["w"] == expect).all()
    assert params["ln"]["scale"].shape == (3, 8)
    assert specs == {"w": ("layers", None, "ff"), "ln": {"scale": ("layers", None)}}
