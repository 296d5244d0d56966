"""Operator-DAG dataflow: chain-equivalence, branches/joins, early
exits, and model cascades.

The lockdown has four parts:

1. **Generative chain-equivalence** — a seeded sampler (same
   ``conftest.py``-style discipline as ``test_engine_parity``) draws
   random *chain* models and engine configurations spanning transfer ×
   micro-batch × fabric × arrivals × cache × tenants, then expresses
   each model two ways: the implicit chain (``preds=None``) and the same
   chain written as an explicit operator DAG (``preds=(i-1,)``,
   ``exit_prob=0.0``).  Both runs must be **bit-for-bit identical** —
   the DAG generalization is only allowed to exist where the graph is
   genuinely not a chain.
2. **DAG properties** (hypothesis-or-shim) — conservation of requests
   under early exits, seeded exit determinism against a direct
   ``_exit_draw`` recomputation, and topological validity of
   ``build_stage_dag`` over sampled cut lists.
3. **Join timing** — a single request through a branched plan on
   distinct nodes finishes exactly when the engine's own stage table
   says the slowest predecessor chain allows (bit-exact float
   recomputation).
4. **Pinned branched run + cascade** — a branched plan dispatches the
   golden event count and fingerprint, and a two-model cascade escalates
   exactly the cheap tenant's misses into the expensive tenant at their
   finish times.

A failing sampled config prints its seed and index; replay with
``_config_at(SAMPLER_SEED, index)``."""

import random

import numpy as np
import pytest
from conftest import fingerprint, given, settings, st

from repro.core.cluster import make_paper_cluster, make_synthetic_cluster
from repro.core.engine import EngineConfig, StageTable
from repro.core import engine as eng_mod
from repro.core.partitioner import ModelPartitioner, build_stage_dag
from repro.core.pipeline import DistributedInference
from repro.core.tenancy import TenantRegistry, TenantTraffic
from repro.core.traffic import (BurstyArrivals, DeterministicArrivals,
                                PoissonArrivals, TraceArrivals)
from repro.models.graph import (LayerSpec, ModelGraph, branched_graph,
                                mobilenetv2_graph)

#: the generative space's seed — part of every failure's reproduction
#: string, never change without regenerating expectations
SAMPLER_SEED = 20260810

#: total sampled configurations (tier-1 runs the first TIER1_CONFIGS of
#: the same sequence; the slow sweep runs the rest in chunks)
NUM_CONFIGS = 120
TIER1_CONFIGS = 8
CHUNK = 28


# --- 1. generative chain-equivalence -----------------------------------------

def _sample_config(rnd: random.Random) -> dict:
    """One (chain model, engine configuration) draw. Uses only the
    passed ``Random`` so config i is a pure function of
    (SAMPLER_SEED, i)."""
    L = rnd.randint(5, 12)
    return dict(
        costs=[round(rnd.uniform(0.5, 30.0), 3) * 1e6 for _ in range(L)],
        out_bytes=[rnd.choice((1 << 12, 1 << 14, 1 << 16, 1 << 18))
                   for _ in range(L)],
        transfer=rnd.choice(("legacy", "serial", "overlap")),
        micro_batch=rnd.choice((1, 2, 4, 8)),
        adaptive_batch=rnd.random() < 0.4,
        fabric=rnd.choice(("isolated", "shared", "maxmin")),
        arrivals_kind=rnd.choice(("closed", "det", "poisson", "mmpp",
                                  "trace")),
        arrival_rate=round(rnd.uniform(1.0, 10.0), 2),
        arrival_seed=rnd.randrange(1 << 16),
        n_tenants=rnd.choice((1, 1, 2)),
        n_nodes=rnd.choice((4, 5, 6)),
        cluster_seed=rnd.randrange(1 << 16),
        n_requests=rnd.choice((30, 50, 80)),
        concurrency=rnd.choice((2, 4, 8)),
        repeat_rate=rnd.choice((0.0, 0.3)),
        use_cache=rnd.random() < 0.3,
        stream_seed=rnd.randrange(1 << 16),
    )


def _config_at(seed: int, index: int) -> dict:
    """Replay the sampler: the config at ``index`` of the seeded
    sequence — the reproduction recipe printed on failure."""
    rnd = random.Random(seed)
    for _ in range(index):
        _sample_config(rnd)
    return _sample_config(rnd)


def _chain_pair(cfg: dict):
    """The sampled chain model expressed twice: implicit chain and the
    same layers as an explicit DAG (``preds``/``exit_prob`` spelled
    out). The second must normalize back to ``is_chain``."""
    spec = list(zip(cfg["costs"], cfg["out_bytes"]))
    plain = ModelGraph("eqchain", [
        LayerSpec(f"l{i}", "Linear", 2048, c, out_bytes=ob)
        for i, (c, ob) in enumerate(spec)])
    dagged = ModelGraph("eqchain", [
        LayerSpec(f"l{i}", "Linear", 2048, c, out_bytes=ob,
                  preds=(i - 1,) if i else (), exit_prob=0.0)
        for i, (c, ob) in enumerate(spec)])
    return plain, dagged


def _make_arrivals(cfg: dict, tenant_idx: int):
    kind = cfg["arrivals_kind"]
    rate = cfg["arrival_rate"]
    seed = cfg["arrival_seed"] + tenant_idx
    if kind == "closed":
        return None
    if kind == "det":
        return DeterministicArrivals.at_rate(rate)
    if kind == "poisson":
        return PoissonArrivals(rate_rps=rate, seed=seed)
    if kind == "mmpp":
        return BurstyArrivals(on_rate_rps=rate * 2.0, off_rate_rps=0.0,
                              mean_on_ms=800.0, mean_off_ms=600.0,
                              seed=seed)
    rnd = random.Random(seed)
    gaps = [rnd.uniform(0.2, 2000.0 / max(rate, 0.5)) for _ in
            range(cfg["n_requests"])]
    return TraceArrivals(np.cumsum(gaps))


def _run(graph: ModelGraph, cfg: dict):
    """Run ``graph`` under ``cfg``; returns (MultiTenantReport, event
    count) or a stringified failure (both graphs must then fail
    identically)."""
    cluster = make_synthetic_cluster(cfg["n_nodes"],
                                     seed=cfg["cluster_seed"] % 1000)
    reg = TenantRegistry(cluster)
    eng_mod.LAST_EVENT_COUNT = None
    try:
        for i in range(cfg["n_tenants"]):
            reg.add(f"t{i}", ModelPartitioner(graph),
                    traffic=TenantTraffic(
                        num_requests=cfg["n_requests"],
                        repeat_rate=cfg["repeat_rate"],
                        seed=cfg["stream_seed"] + i,
                        concurrency=cfg["concurrency"],
                        arrivals=_make_arrivals(cfg, i)),
                    num_partitions=3, method="planner",
                    use_cache=cfg["use_cache"])
        engine_cfg = EngineConfig(
            transfer=cfg["transfer"], micro_batch=cfg["micro_batch"],
            fabric=cfg["fabric"], adaptive_batch=cfg["adaptive_batch"])
        result = reg.run(engine=engine_cfg)
    except Exception as e:   # both graphs must fail the same way
        return f"{type(e).__name__}: {e}", None
    return result, eng_mod.LAST_EVENT_COUNT


def _assert_chain_equivalence(index: int):
    cfg = _config_at(SAMPLER_SEED, index)
    repro = (f"config {index} of sampler seed {SAMPLER_SEED} — replay "
             f"with tests.test_dag._config_at({SAMPLER_SEED}, {index}) "
             f"= {cfg!r}")
    plain, dagged = _chain_pair(cfg)
    assert dagged.is_chain, (
        f"explicit (i-1)-preds chain failed to normalize\n{repro}")
    ref, ref_ev = _run(plain, cfg)
    res, nev = _run(dagged, cfg)
    if isinstance(ref, str) or isinstance(res, str):
        assert ref == res, (
            f"failure modes disagree for the dagged graph — reference: {ref!r}, "
            f"got: {res!r}\n{repro}")
        return
    assert ref_ev == nev, (
        f"event counts differ for the dagged graph: {ref_ev} vs {nev}\n{repro}")
    assert set(ref.reports) == set(res.reports), repro
    for name, h in ref.reports.items():
        f = res.reports[name]
        assert h.columns.bitwise_equal(f.columns), (
            f"RequestColumns differ for tenant {name!r}\n{repro}")
        assert h.batch_hist == f.batch_hist, repro
        assert h.network_bytes == f.network_bytes, repro
        hq, fq = h.queue_depth, f.queue_depth
        assert (hq is None) == (fq is None), repro
        if hq is not None:
            assert (np.array_equal(hq[0], fq[0])
                    and np.array_equal(hq[1], fq[1])), repro
        assert h.fabric_stats == f.fabric_stats, repro


@pytest.mark.parametrize("index", range(TIER1_CONFIGS))
def test_chain_equivalence_tier1(index):
    """A chain written as an explicit DAG runs the original chain code
    bit-for-bit — the always-on degeneracy gate."""
    _assert_chain_equivalence(index)


@pytest.mark.slow
@pytest.mark.parametrize("lo", range(TIER1_CONFIGS, NUM_CONFIGS, CHUNK))
def test_chain_equivalence_sweep(lo):
    """The remaining sampled configurations, in chunks — the full
    generative equivalence sweep (deselect with ``-m 'not slow'``)."""
    for index in range(lo, min(lo + CHUNK, NUM_CONFIGS)):
        _assert_chain_equivalence(index)


def test_sampler_is_deterministic():
    """Config i is a pure function of (seed, i) — the reproduction
    contract the failure messages rely on."""
    assert _config_at(SAMPLER_SEED, 9) == _config_at(SAMPLER_SEED, 9)
    assert _config_at(SAMPLER_SEED, 9) != _config_at(SAMPLER_SEED, 10)
    assert (_sample_config(random.Random(SAMPLER_SEED))
            == _config_at(SAMPLER_SEED, 0))


# --- 2. DAG properties --------------------------------------------------------

def _expected_exit(seed: int, r: int, graph: ModelGraph) -> int:
    """Direct recomputation of request ``r``'s exit head: walk the exit
    heads in layer order, first successful seeded draw wins — the
    engine must agree regardless of cuts or event order."""
    for e, l in enumerate(graph.layers):
        if l.exit_prob > 0.0:
            if eng_mod._exit_draw(seed, r, ((e, l.exit_prob),)) == e:
                return e
    return -1


@settings(max_examples=10, deadline=None)
@given(exit_prob=st.floats(min_value=0.05, max_value=0.9),
       seed=st.integers(min_value=0, max_value=1 << 16),
       mb=st.integers(min_value=1, max_value=4))
def test_exit_conservation_and_determinism(exit_prob, seed, mb):
    """Every request exits at a declared head or the tail (counts sum to
    n) and the exit column matches the direct seeded recomputation."""
    g = branched_graph(exit_prob=round(exit_prob, 3))
    heads = {i for i, l in enumerate(g.layers) if l.exit_prob > 0.0}
    n = 60
    expect = np.array([_expected_exit(seed, r, g) for r in range(n)])
    d = DistributedInference(make_paper_cluster(), ModelPartitioner(g),
                             method="planner")
    rep = d.run(n, seed=seed, concurrency=4,
                engine=EngineConfig(micro_batch=mb))
    assert set(np.unique(rep.columns.exit_head)) <= heads | {-1}
    counts = rep.exit_counts()
    assert sum(counts.values()) == n
    assert np.array_equal(rep.columns.exit_head, expect)
    assert rep.early_exit_rate == pytest.approx(
        float(np.mean(expect >= 0)))


def test_exit_draw_is_event_order_independent():
    """The exit column is a pure function of (stream seed, request id,
    head) — scrambling the schedule via micro-batch, transfer mode, and
    the repeat-rate RNG must not move a single exit."""
    g = branched_graph(exit_prob=0.4)
    cols = []
    for mb in (1, 4):
        for transfer in ("legacy", "overlap"):
            for rr in (0.0, 0.3):
                d = DistributedInference(make_paper_cluster(),
                                         ModelPartitioner(g),
                                         method="planner")
                rep = d.run(80, seed=5, repeat_rate=rr, concurrency=4,
                            engine=EngineConfig(transfer=transfer,
                                                micro_batch=mb))
                cols.append(rep.columns.exit_head)
    for c in cols[1:]:
        assert np.array_equal(cols[0], c)


@settings(max_examples=25, deadline=None)
@given(trunk=st.integers(min_value=1, max_value=3),
       arms=st.integers(min_value=2, max_value=3),
       arm_len=st.integers(min_value=1, max_value=3),
       tail=st.integers(min_value=1, max_value=3),
       ncuts=st.integers(min_value=0, max_value=5),
       seed=st.integers(min_value=0, max_value=9999))
def test_sampled_cuts_build_valid_stage_dags(trunk, arms, arm_len, tail,
                                             ncuts, seed):
    """Every strictly-increasing cut list over a validated operator DAG
    yields a structurally sound stage DAG: forward edges, join arities
    matching the in-edges, exit heads homed in their containing stage,
    and reach probabilities in (0, 1] starting at certainty."""
    g = branched_graph(trunk=trunk, arms=arms, arm_len=arm_len, tail=tail,
                       exit_prob=0.25)
    L = len(g.layers)
    rnd = random.Random(seed)
    inner = sorted(rnd.sample(range(1, L), min(ncuts, L - 1)))
    cuts = [0] + inner + [L]
    dag = build_stage_dag(g, cuts)
    S = len(cuts) - 1
    n_in = [0] * S
    for si, edges in enumerate(dag.succs):
        seen = set()
        for sj, b in edges:
            assert si < sj < S, f"edge ({si}, {sj}) not forward"
            assert sj not in seen, "duplicate stage edge not coalesced"
            seen.add(sj)
            assert b > 0
            n_in[sj] += 1
    assert list(dag.pred_counts) == n_in
    assert dag.pred_counts[0] == 0
    placed = [h for heads in dag.exit_heads for h in heads]
    declared = [(e, l.exit_prob) for e, l in enumerate(g.layers)
                if l.exit_prob > 0.0]
    assert sorted(placed) == sorted(declared)
    for si, heads in enumerate(dag.exit_heads):
        for e, _p in heads:
            assert cuts[si] <= e < cuts[si + 1]
    assert dag.reach[0] == 1.0
    assert all(0.0 < r <= 1.0 for r in dag.reach)


def test_degenerate_cuts_on_chain_have_no_stage_dag():
    """plan_from_cuts on a chain never grows a stage DAG — the planner's
    and engine's DAG branches stay unreachable for chain graphs."""
    part = ModelPartitioner(mobilenetv2_graph())
    assert part.plan(3, method="optimal").stage_dag is None
    assert part.plan_from_cuts([0, 40, 141]).stage_dag is None


# --- 3. join timing -----------------------------------------------------------

def _branched_pipeline(core_cluster_seed=11):
    """A 4-stage branched plan (trunk | arm0 | arm1 | join+tail) pinned
    to four distinct nodes — stage boundaries and placement explicit so
    the expected timeline is reconstructible."""
    g = branched_graph(trunk=2, arms=2, arm_len=2, tail=2, exit_prob=0.0)
    cuts = [0, 2, 4, 6, len(g.layers)]
    cluster = make_synthetic_cluster(6, seed=core_cluster_seed)
    part = ModelPartitioner(g)
    d = DistributedInference(cluster, part, num_partitions=4)
    d.plan = part.plan_from_cuts(cuts)
    nids = list(cluster.nodes)[:4]
    d.placement = d.deployer.deploy_plan(d.plan, nids)
    return d


def test_join_waits_for_slowest_predecessor_bit_exact():
    """One request through the branched plan on idle distinct nodes:
    each stage starts at the max over predecessor arrivals (end +
    per-edge transfer), and the engine's finish time equals that forward
    recomputation float-for-float."""
    from repro.core.scheduler import SCHEDULING_OVERHEAD_MS
    d = _branched_pipeline()
    rep = d.run(1, concurrency=1)
    table = StageTable(d, 0)
    S = len(table.stages)
    assert not table.chain
    arrive = [None] * S
    # the paper's per-request scheduling decision precedes stage 0
    arrive[0] = float(rep.columns.submit_ms[0]) + SCHEDULING_OVERHEAD_MS
    end = [None] * S
    for si in range(S):
        assert arrive[si] is not None, f"stage {si} never fed"
        end[si] = arrive[si] + table.stages[si].exec_ms
        for e in (table.stages[si].succs or ()):
            a = end[si] + e.xfer_ms
            j = e.next_index
            arrive[j] = a if arrive[j] is None else max(arrive[j], a)
    # the join genuinely waited: the asymmetric arms arrive apart
    assert arrive[3] > min(end[1] + table.stages[1].succs[0].xfer_ms,
                           end[2] + table.stages[2].succs[0].xfer_ms)
    assert float(rep.columns.finish_ms[0]) == end[S - 1], (
        f"finish {float(rep.columns.finish_ms[0])!r} != "
        f"recomputed {end[S - 1]!r}")


# --- 4. pinned branched run + cascades ----------------------------------------

#: the branched run below: its event count and fingerprint, recorded where
#: the heap loop and a since-removed time-wheel core (which had to refuse
#: its chain fusion on DAG tables) dispatched identical event streams
BRANCHED_EVENTS = 278
BRANCHED_GOLDEN = (BRANCHED_EVENTS, None, (
    ('', 50, 0, 0, 216781.168, 1292.60515, 9699328.0, None,
     ((1, 86), (2, 19))),))


def test_fast_core_event_count_pins_to_oracle_on_branched_plan():
    """A branched plan with early exits and 2-way micro-batching
    dispatches exactly the golden event count and reproduces its golden
    fingerprint (counts, finish-time sum, p99 sojourn, network bytes,
    batch histogram)."""
    g = branched_graph(exit_prob=0.3)
    eng_mod.LAST_EVENT_COUNT = None
    d = DistributedInference(make_paper_cluster(), ModelPartitioner(g),
                             method="planner")
    rep = d.run(50, seed=7, concurrency=4,
                engine=EngineConfig(micro_batch=2))
    assert eng_mod.LAST_EVENT_COUNT == BRANCHED_EVENTS
    assert fingerprint(rep, eng_mod.LAST_EVENT_COUNT) == BRANCHED_GOLDEN


def _cascade_registry(graph_cheap, n=120):
    cluster = make_paper_cluster()
    reg = TenantRegistry(cluster)
    reg.add("cheap", ModelPartitioner(graph_cheap),
            traffic=TenantTraffic(num_requests=n, seed=3, concurrency=4,
                                  escalate_to="big"),
            num_partitions=3, method="planner")
    reg.add("big", ModelPartitioner(mobilenetv2_graph()),
            traffic=TenantTraffic(num_requests=n, seed=9, concurrency=4),
            num_partitions=3, method="planner")
    return reg


def test_cascade_escalates_exactly_the_misses():
    """Two-model cascade: every cheap-tenant request that runs to the
    tail (no exit head fired) re-enters the expensive tenant at its
    finish time; the expensive tenant serves exactly those."""
    res = _cascade_registry(branched_graph(exit_prob=0.6)).run()
    cheap, big = res.reports["cheap"], res.reports["big"]
    miss = cheap.columns.exit_head == -1
    assert int(miss.sum()) == len(big.columns) > 0
    assert len(big.columns) < len(cheap.columns)
    # escalations enter the big tenant at the cheap finish times
    assert np.array_equal(np.sort(big.columns.submit_ms),
                          np.sort(cheap.columns.finish_ms[miss]))
    assert (big.columns.exit_head == -1).all()


def test_cascade_with_no_misses_is_an_error():
    """A cascade whose target receives zero escalations is a
    misconfiguration (the expensive tenant's stream would be empty) and
    must fail loudly."""
    g = branched_graph(exit_prob=0.999)   # virtually everything exits
    with pytest.raises(RuntimeError) as ei:
        _cascade_registry(g, n=20).run()
    assert "escalated" in str(ei.value)


def test_dag_restrictions_are_enforced():
    """DAG plans reject the result cache and non-isolated fabrics, and
    ``run_legacy`` refuses DAG graphs outright — the unsupported
    combinations fail loudly instead of drifting silently."""
    g = branched_graph(exit_prob=0.3)
    cached = DistributedInference(make_paper_cluster(), ModelPartitioner(g),
                                  method="planner", use_cache=True)
    with pytest.raises(ValueError):
        cached.run(10)
    plainer = DistributedInference(make_paper_cluster(), ModelPartitioner(g),
                                   method="planner")
    with pytest.raises(ValueError):
        plainer.run(10, engine=EngineConfig(fabric="shared"))
    with pytest.raises(AssertionError):
        plainer.run_legacy(10)


def test_report_exit_head_accounting():
    """RunReport's per-exit-head accounting: counts sum to n, goodput
    decomposes over heads, and the flattened row carries the early-exit
    extras."""
    g = branched_graph(exit_prob=0.4)
    d = DistributedInference(make_paper_cluster(), ModelPartitioner(g),
                             method="planner")
    rep = d.run(90, seed=11, concurrency=4)
    counts = rep.exit_counts()
    assert sum(counts.values()) == 90
    assert set(counts) > {-1}
    gp = rep.goodput_by_exit(2000.0)
    assert set(gp) == set(counts)
    assert all(v >= 0.0 for v in gp.values())
    row = rep.row()
    assert row["early_exit_rate"] == pytest.approx(rep.early_exit_rate, abs=1e-4)
