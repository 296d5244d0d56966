"""Model Partitioner: paper-exact reproduction + hypothesis property tests."""

import math

import pytest
from conftest import given, settings, st

from repro.core.partitioner import ModelPartitioner
from repro.models.graph import LayerSpec, ModelGraph, mobilenetv2_graph, transformer_graph
from repro.configs import get_config


# --- paper §IV-D: exact partition-size reproduction -------------------------

def test_mobilenetv2_has_141_leaf_layers():
    g = mobilenetv2_graph()
    assert len(g.layers) == 141
    kinds = {}
    for l in g.layers:
        kinds[l.kind] = kinds.get(l.kind, 0) + 1
    assert kinds == {"Conv2d": 52, "BatchNorm2d": 52, "ReLU6": 35,
                     "Dropout": 1, "Linear": 1}


def test_paper_partition_sizes_2way():
    plan = ModelPartitioner(mobilenetv2_graph()).plan(2)
    assert plan.sizes == [116, 25]          # paper §IV-D


def test_paper_partition_sizes_3way():
    plan = ModelPartitioner(mobilenetv2_graph()).plan(3)
    assert plan.sizes == [108, 16, 17]      # paper §IV-D


def test_partition_4way_covers_all_layers():
    plan = ModelPartitioner(mobilenetv2_graph()).plan(4)
    assert sum(plan.sizes) == 141 and len(plan.sizes) == 4


# --- property tests over random layer graphs --------------------------------

def _graph_from_costs(costs):
    g = ModelGraph("rand")
    g.layers = [LayerSpec(f"l{i}", "Linear", 1, float(c), out_bytes=4)
                for i, c in enumerate(costs)]
    return g


costs_strategy = st.lists(st.floats(min_value=0.0, max_value=1e6,
                                    allow_nan=False, allow_infinity=False),
                          min_size=2, max_size=200)


@given(costs=costs_strategy, n=st.integers(1, 8))
@settings(max_examples=200, deadline=None)
def test_boundaries_are_contiguous_and_exhaustive(costs, n):
    n = min(n, len(costs))
    p = ModelPartitioner(_graph_from_costs(costs))
    cuts = p.boundaries(n)
    assert cuts[0] == 0 and cuts[-1] == len(costs)
    assert all(a <= b for a, b in zip(cuts, cuts[1:]))
    assert len(cuts) == n + 1


@given(costs=costs_strategy, n=st.integers(1, 8))
@settings(max_examples=200, deadline=None)
def test_plan_conserves_cost_and_layers(costs, n):
    n = min(n, len(costs))
    p = ModelPartitioner(_graph_from_costs(costs))
    plan = p.plan(n)
    assert sum(plan.sizes) == len(costs)
    assert math.isclose(sum(plan.costs), sum(costs), rel_tol=1e-9, abs_tol=1e-6)


@given(costs=costs_strategy, n=st.integers(2, 6))
@settings(max_examples=100, deadline=None)
def test_greedy_partitions_meet_target_except_last(costs, n):
    """Paper Eq. 3: every closed partition's cost >= target (layers are added
    until the cumulative cost meets/exceeds it)."""
    n = min(n, len(costs))
    p = ModelPartitioner(_graph_from_costs(costs))
    plan = p.plan(n)
    target = sum(costs) / n
    for part in plan.partitions[:-1]:
        if part.hi <= len(costs) and part.num_layers > 0 and part.hi != part.lo:
            # closed partitions reached the target unless the model ran out
            if part.hi < len(costs):
                assert part.cost >= target - 1e-6 or part.cost == 0.0


@given(costs=st.lists(st.floats(min_value=1.0, max_value=1e5,
                                allow_nan=False), min_size=4, max_size=120),
       n=st.integers(2, 6))
@settings(max_examples=100, deadline=None)
def test_optimal_bottleneck_not_worse_than_greedy(costs, n):
    n = min(n, len(costs))
    p = ModelPartitioner(_graph_from_costs(costs))
    greedy = p.plan(n).costs
    opt = p.plan(n, method="optimal").costs
    assert max(opt) <= max(greedy) + 1e-6


@given(costs=st.lists(st.floats(min_value=1.0, max_value=1e5,
                                allow_nan=False), min_size=4, max_size=120),
       n=st.integers(2, 6))
@settings(max_examples=100, deadline=None)
def test_refine_never_increases_bottleneck(costs, n):
    n = min(n, len(costs))
    p = ModelPartitioner(_graph_from_costs(costs))
    cuts = p.boundaries(n)
    refined = p.refine(cuts)
    def bott(c):
        return max(sum(costs[c[i]:c[i+1]]) for i in range(n))
    assert bott(refined) <= bott(cuts) + 1e-6


@given(n=st.integers(2, 6), w=st.lists(st.floats(0.2, 2.0), min_size=2, max_size=6))
@settings(max_examples=50, deadline=None)
def test_weighted_targets_shift_boundaries(n, w):
    g = mobilenetv2_graph()
    p = ModelPartitioner(g)
    n = min(n, len(w))
    plan = p.plan(n, weights=w[:n])
    assert sum(plan.sizes) == len(g.layers)


# --- transformer graphs -------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen2-7b", "mamba2-130m", "kimi-k2-1t-a32b",
                                  "recurrentgemma-9b", "whisper-medium",
                                  "llama-3.2-vision-90b", "deepseek-v2-236b",
                                  "granite-4.0-h-small"])
def test_transformer_graph_partitionable(arch):
    cfg = get_config(arch)
    g = transformer_graph(cfg, batch=1, seq=2048)
    p = ModelPartitioner(g)
    plan = p.plan(4)
    assert sum(plan.sizes) == len(g.layers)
    assert plan.imbalance < 3.0
    assert g.total_flops > 0


# --- degenerate cases --------------------------------------------------------

def test_boundaries_empty_tail_when_front_layers_absorb_targets():
    """One dominant layer swallows every target: the greedy pass closes the
    remaining partitions empty at the tail, but coverage is preserved."""
    p = ModelPartitioner(_graph_from_costs([100.0, 1.0, 1.0, 1.0]))
    cuts = p.boundaries(4)
    assert cuts[0] == 0 and cuts[-1] == 4 and len(cuts) == 5
    assert all(a <= b for a, b in zip(cuts, cuts[1:]))
    plan = p.plan(4)
    assert sum(plan.sizes) == 4
    assert 0 in plan.sizes                 # at least one empty tail partition
    assert sum(plan.costs) == pytest.approx(103.0)


def test_refine_weighted_never_worse_than_input():
    g = mobilenetv2_graph()
    p = ModelPartitioner(g)
    costs = [l.cost for l in g.layers]
    weights = [1.0, 0.6, 0.4]
    cuts = p.boundaries(3, weights=weights)
    refined = p.refine(cuts, weights=weights)

    def bottleneck(c):
        return max(sum(costs[c[i]:c[i + 1]]) / weights[i] for i in range(3))

    assert bottleneck(refined) <= bottleneck(cuts) + 1e-6


def test_optimal_not_worse_than_greedy_on_mobilenetv2():
    g = mobilenetv2_graph()
    p = ModelPartitioner(g)
    costs = [l.cost for l in g.layers]
    for n in (2, 3, 4):
        for weights in (None, [1.0] * n, list(range(1, n + 1))):
            greedy = p.boundaries(n, weights=weights)
            opt = p.optimal_boundaries(n, weights=weights)
            w = weights or [1.0] * n

            def bottleneck(c):
                return max(sum(costs[c[i]:c[i + 1]]) / w[i] for i in range(n))

            assert bottleneck(opt) <= bottleneck(greedy) + 1e-6
            assert opt[0] == 0 and opt[-1] == len(costs)


def test_recalibration_blends_observed_time():
    p = ModelPartitioner(mobilenetv2_graph())
    assert p.calibration == 1.0
    p.recalibrate(observed_ms=200.0, predicted_ms=100.0)
    assert 1.0 < p.calibration < 2.0
