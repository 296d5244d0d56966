"""Event-driven pipeline engine: parity with the legacy loop, transfer
overlap, micro-batching, table invalidation, the cache satellites, and
oracle-free properties of the event loop (conservation, per-node FIFO
order, goodput bounded by offered load, determinism)."""

import random

import numpy as np
import pytest

from repro.core.cache import ResultCache, digest
from repro.core.cluster import make_paper_cluster, make_synthetic_cluster
from repro.core.engine import EngineConfig, PipelineEngine, StageTable
from repro.core.adaptation import (cpu_throttle, latency_spike, node_death,
                                   node_recovery)
from repro.core.partitioner import ModelPartitioner
from repro.core.pipeline import DistributedInference, run_monolithic
from repro.core.tenancy import TenantRegistry, TenantTraffic
from repro.core.traffic import DeterministicArrivals, PoissonArrivals
from repro.models.graph import mobilenetv2_graph

CONCURRENCY = 4          # closed-loop window for the scenario runs

#: explicit stage->node assignment used by the transfer-mode tests: the
#: bottleneck stage (on the 0.4-CPU node) *sends* a boundary, so blocking
#: vs. overlapped transfer semantics are distinguishable in steady state
BOTTLENECK_SENDS = ["edge-2-low", "edge-0-high", "edge-1-medium"]

COLUMNS = ("submit_ms", "finish_ms", "comm_ms", "service_ms",
           "cache_hits", "stages")


@pytest.fixture(scope="module")
def graph():
    return mobilenetv2_graph()


def _fresh(graph, **kw):
    return DistributedInference(make_paper_cluster(),
                                ModelPartitioner(graph), **kw)


def _assert_bit_equal(rep_legacy, rep_engine):
    c1, c2 = rep_legacy.columns, rep_engine.columns
    for f in COLUMNS:
        a, b = getattr(c1, f), getattr(c2, f)
        assert np.array_equal(a, b), (
            f"column {f} diverges at requests "
            f"{np.flatnonzero(a != b)[:5].tolist()}")
    assert rep_legacy.network_bytes == rep_engine.network_bytes


def _run_both(graph, scenario_fn=None, warm=0, run_kw=None, n=60, **kw):
    """Run the legacy loop and the default engine from identical fresh
    state; returns (legacy_report, engine_report, legacy_pipe, engine_pipe)."""
    run_kw = run_kw or {}
    out = []
    for method in ("run_legacy", "run"):
        d = _fresh(graph, **kw)
        if warm:
            getattr(d, method)(warm, name="warm", concurrency=CONCURRENCY)
        scenario = scenario_fn(d) if scenario_fn else None
        rep = getattr(d, method)(n, scenario=scenario, **run_kw)
        out.extend([rep, d])
    return out[0], out[2], out[1], out[3]


# --- bit-for-bit parity (overlap / micro-batching disabled) -------------------

def test_parity_plain_stream(graph):
    rep_l, rep_e, _, _ = _run_both(graph)
    _assert_bit_equal(rep_l, rep_e)


def test_parity_cache_stream(graph):
    rep_l, rep_e, d_l, d_e = _run_both(
        graph, run_kw=dict(repeat_rate=0.8), use_cache=True)
    _assert_bit_equal(rep_l, rep_e)
    assert rep_e.cache_stats == rep_l.cache_stats
    assert rep_e.cache_stats["hit_rate"] > 0.3


def test_parity_adaptive_node_death(graph):
    def death(d):
        t0 = d.cluster.clock.now_ms
        return [node_death(t0 + 50.0, d.placement[max(d.placement)])]
    rep_l, rep_e, d_l, d_e = _run_both(
        graph, scenario_fn=death, warm=12,
        run_kw=dict(concurrency=CONCURRENCY), adaptive=True)
    _assert_bit_equal(rep_l, rep_e)
    assert d_e.controller.migrations == d_l.controller.migrations == 1


def test_parity_nonadaptive_node_death(graph):
    def death(d):
        t0 = d.cluster.clock.now_ms
        return [node_death(t0 + 50.0, d.placement[max(d.placement)])]
    rep_l, rep_e, _, _ = _run_both(
        graph, scenario_fn=death, warm=12,
        run_kw=dict(concurrency=CONCURRENCY))
    _assert_bit_equal(rep_l, rep_e)


def test_parity_death_recovery_cycle(graph):
    def death_recovery(d):
        t0 = d.cluster.clock.now_ms
        victim = d.placement[max(d.placement)]
        return [node_death(t0 + 50.0, victim),
                node_recovery(t0 + 4000.0, victim)]
    rep_l, rep_e, d_l, d_e = _run_both(
        graph, scenario_fn=death_recovery, warm=12,
        run_kw=dict(concurrency=CONCURRENCY), adaptive=True)
    _assert_bit_equal(rep_l, rep_e)
    assert d_e.controller.migrations == d_l.controller.migrations == 2


def test_parity_cpu_throttle(graph):
    def throttle(d):
        t0 = d.cluster.clock.now_ms
        return [cpu_throttle(t0 + 50.0, "edge-0-high")]
    rep_l, rep_e, d_l, d_e = _run_both(
        graph, scenario_fn=throttle, warm=12,
        run_kw=dict(concurrency=CONCURRENCY), adaptive=True)
    _assert_bit_equal(rep_l, rep_e)
    assert d_e.controller.migrations == d_l.controller.migrations


def test_parity_planner_placement(graph):
    rep_l, rep_e, _, _ = _run_both(graph, method="planner")
    _assert_bit_equal(rep_l, rep_e)


# --- transfer policies and micro-batching ------------------------------------

def _mode_run(graph, n=300, engine=None):
    d = _fresh(graph, num_partitions=3, assignment=list(BOTTLENECK_SENDS))
    return d.run(n, engine=engine)


def test_overlap_beats_serial_transfer(graph):
    """DEFER's claim: overlapping boundary transfer with the sender's next
    compute strictly improves steady-state throughput over the naive
    blocking-send runtime."""
    serial = _mode_run(graph, engine=EngineConfig(transfer="serial"))
    overlap = _mode_run(graph, engine=EngineConfig(transfer="overlap"))
    assert overlap.tail_throughput_rps() > serial.tail_throughput_rps()


def test_overlap_microbatch_beats_legacy_loop(graph):
    """Overlap + micro-batching strictly improves steady-state throughput
    over the legacy loop on the paper's 3-node testbed (fixed per-inference
    overhead amortized k-way at the bottleneck stage)."""
    d = _fresh(graph, num_partitions=3, assignment=list(BOTTLENECK_SENDS))
    legacy = d.run_legacy(300)
    ovmb = _mode_run(graph, engine=EngineConfig(transfer="overlap",
                                                micro_batch=4))
    assert ovmb.tail_throughput_rps() > legacy.tail_throughput_rps()


def test_overlap_equals_legacy_without_batching(graph):
    """With micro-batching off, the async-link model and the legacy
    accounting agree in steady state on the testbed (links are never the
    bottleneck there) — overlap's win comes from not *blocking*, which the
    legacy accounting already assumed optimistically."""
    legacy = _mode_run(graph, engine=None)
    overlap = _mode_run(graph, engine=EngineConfig(transfer="overlap"))
    assert overlap.tail_throughput_rps() == pytest.approx(
        legacy.tail_throughput_rps(), rel=1e-6)


def test_execution_ms_vec_matches_scalar_model():
    """The vectorized cost model is pinned element-wise against the scalar
    one, including the superlinear memory-pressure branch."""
    from repro.core.cost_model import PROFILES, execution_ms, execution_ms_vec
    profile = PROFILES["low"]
    costs = np.array([1e5, 5e6, 2e7, 8e7])
    ws = np.array([0.0, 1e8, profile.mem_bytes * 1.5, profile.mem_bytes * 4])
    vec = execution_ms_vec(costs, profile, ws)
    for i in range(len(costs)):
        assert vec[i] == pytest.approx(
            execution_ms(float(costs[i]), profile, float(ws[i])), rel=1e-12)


def test_microbatch_amortizes_fixed_overhead(graph):
    """exec_for(k) charges one fixed per-inference overhead for k coalesced
    requests; xfer_for(k) charges one per-message network latency."""
    from repro.core.cost_model import FIXED_OVERHEAD_MS
    d = _fresh(graph, num_partitions=3)
    engine = PipelineEngine(d)
    table = engine._current_table()
    st = table.stages[0]
    e1, e4 = st.exec_for(1), st.exec_for(4)
    assert e4 == pytest.approx(4 * (e1 - FIXED_OVERHEAD_MS)
                               + FIXED_OVERHEAD_MS)
    x1, x4 = st.xfer_for(1), st.xfer_for(4)
    lat = st.recv_node.profile.net_latency_ms
    assert x4 == pytest.approx(4 * (x1 - lat) + lat)


def test_exec_for_matches_batch_cost_model(graph):
    """StageEntry.exec_for/xfer_for agree element-wise with the shared
    ``BatchCostModel`` — the same numbers the batch-aware planner
    objective uses, so engine and planner cannot disagree."""
    d = _fresh(graph, num_partitions=3)
    engine = PipelineEngine(d)
    table = engine._current_table()
    for st in table.stages:
        part = st._part
        for k in (1, 2, 4, 8):
            ws = d.partitioner.working_set(part, k)
            want = d.batch_model.exec_ms(
                part.cost * table.batch / table.speedup,
                st.node.profile, ws, k=k)
            assert st.exec_for(k) == pytest.approx(want, rel=1e-12)
            if st.recv_node is not None:
                assert st.xfer_for(k) == pytest.approx(
                    d.batch_model.xfer_ms(st.out_bytes, st.recv_node.profile,
                                          k=k), rel=1e-12)


def test_exec_for_calibrated_curves(graph):
    """With a calibration artifact attached, exec_for(k) follows the
    blended per-stage KindCurve (overhead + per-item scale), not the
    analytic constants — and exec_for(1) is the table's exec_ms."""
    from repro.core.cost_model import BatchCostModel, KindCurve
    m = BatchCostModel({"default": KindCurve(overhead_ms=6.0,
                                             per_item_scale=1.5)},
                       source="unit-test")
    d = _fresh(graph, num_partitions=3, batch_model=m)
    table = PipelineEngine(d)._current_table()
    assert table.batch_model is m
    for st in table.stages:
        part = st._part
        curve = m.partition_curve(graph, part.lo, part.hi)
        assert st.exec_for(1) == st.exec_ms
        for k in (1, 4):
            ws = d.partitioner.working_set(part, k)
            want = m.exec_ms(part.cost * table.batch / table.speedup,
                             st.node.profile, ws, k=k, curve=curve)
            assert st.exec_for(k) == pytest.approx(want, rel=1e-12)
        # amortization still holds under the calibrated curve
        assert st.exec_for(4) < 4 * st.exec_for(1)


def test_event_mode_cache_serves_hits(graph):
    d = _fresh(graph, use_cache=True)
    rep = d.run(120, repeat_rate=0.8,
                engine=EngineConfig(transfer="overlap", micro_batch=2))
    assert rep.cache_stats["hit_rate"] > 0.3
    assert int(rep.columns.cache_hits.sum()) > 0


def test_event_mode_adaptive_node_death(graph):
    """The controller acts on engine events (scenario mutations, poll
    ticks): a mid-run death still produces exactly one migration and the
    dead node serves nothing afterwards."""
    d = _fresh(graph, adaptive=True)
    d.run(12, name="warm", concurrency=CONCURRENCY,
          engine=EngineConfig(transfer="overlap"))
    t0 = d.cluster.clock.now_ms
    victim = d.placement[max(d.placement)]
    d.run(40, name="fault", concurrency=CONCURRENCY,
          scenario=[node_death(t0 + 50.0, victim)],
          engine=EngineConfig(transfer="overlap"))
    assert d.controller.migrations == 1
    assert victim not in d.placement.values()


def test_adaptive_replan_with_fewer_nodes_than_configured_stages(graph):
    """A death that drops the live node count below the deploy-time stage
    count must still re-plan (shallower), not fail as 'no capacity' — the
    planner clamps max_stages to the surviving nodes."""
    d = _fresh(graph, num_partitions=3, adaptive=True)
    d.run(12, name="warm", concurrency=CONCURRENCY)
    t0 = d.cluster.clock.now_ms
    victim = d.placement[max(d.placement)]
    d.run(30, name="fault", concurrency=CONCURRENCY,
          scenario=[node_death(t0 + 50.0, victim)])
    assert d.controller.migrations == 1
    assert victim not in d.placement.values()
    assert len(d.plan.partitions) <= 2


# --- stage-table caching / invalidation --------------------------------------

def test_stage_table_reused_and_invalidated(graph):
    d = _fresh(graph)
    engine = PipelineEngine(d)
    t1 = engine._current_table()
    assert engine._current_table() is t1          # cached: nothing changed
    d.cluster.set_profile("edge-0-high", cpu=0.5)
    t2 = engine._current_table()
    assert t2 is not t1                           # profile change invalidates
    d.rebalance(method="optimal")
    t3 = engine._current_table()
    assert t3 is not t2                           # re-deploy invalidates
    assert isinstance(t3, StageTable)


def test_profile_change_mid_run_matches_legacy(graph):
    """A latency spike re-prices boundary transfers: the cached table must
    pick up the new profile exactly when the legacy loop does."""
    def spike(d):
        t0 = d.cluster.clock.now_ms
        return [latency_spike(t0 + 50.0, d.placement[1], 40.0)]
    rep_l, rep_e, _, _ = _run_both(
        graph, scenario_fn=spike, warm=12,
        run_kw=dict(concurrency=CONCURRENCY))
    _assert_bit_equal(rep_l, rep_e)


# --- numpy metric columns -----------------------------------------------------

def test_columns_materialize_matches(graph):
    rep = _fresh(graph).run(30)
    reqs = rep.requests                            # lazy materialization
    c = rep.columns
    assert len(reqs) == len(c) == 30
    for i in (0, 7, 29):
        assert reqs[i].submit_ms == c.submit_ms[i]
        assert reqs[i].latency_ms == pytest.approx(
            float(c.finish_ms[i] - c.submit_ms[i]))
    # aggregate properties work off the columns
    assert rep.avg_latency_ms == pytest.approx(
        np.mean(c.finish_ms - c.submit_ms))


# --- satellite: ResultCache stores values, credits bytes in put/get -----------

def test_cache_stores_value_and_credits_bytes():
    cache = ResultCache(capacity=4)
    key = cache.key("m", (0, 10), "sig")
    cache.put(key, {"act": 123}, transfer_bytes=1000.0)
    assert cache.get(key) == {"act": 123}
    assert cache.bytes_saved == 1000.0
    assert cache.get(key) == {"act": 123}
    assert cache.bytes_saved == 2000.0            # credited per hit
    assert cache.get(cache.key("m", (0, 10), "other")) is None
    assert cache.stats()["hits"] == 2 and cache.stats()["misses"] == 1


def test_digest_memoized_per_signature():
    a = np.arange(8, dtype=np.float32)
    b = np.arange(8, dtype=np.float32) + 1.0
    d1 = digest(a, signature="sig-A")
    assert digest(a, signature="sig-A") == d1     # memo hit
    # the signature asserts input identity: the memo answers for it
    assert digest(b, signature="sig-A") == d1
    assert digest(b) != d1                        # unmemoized path rehashes
    assert digest(a) == d1                        # and agrees with the memo


def test_infer_serves_real_activations_from_cache(graph):
    """The executor path: cached entries are actual stage outputs, so a
    repeated input runs zero executor calls and returns the same result."""
    calls = []

    def executor(lo, hi, x, res):
        calls.append((lo, hi))
        return x * 2.0 + (hi - lo), res

    d = _fresh(graph, executor=executor, use_cache=True)
    x = np.ones(4, dtype=np.float64)
    y1 = d.infer(x, signature="req-pattern")
    n_exec = len(calls)
    assert n_exec == len(d.plan.partitions)
    y2 = d.infer(x, signature="req-pattern")
    assert len(calls) == n_exec                   # served fully from cache
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert d.cache.bytes_saved > 0


# --- satellite: run_monolithic routes node_id through the deployer ------------

def test_run_monolithic_placement_via_deployer(graph):
    cluster = make_paper_cluster()
    rep = run_monolithic(cluster, ModelPartitioner(graph), 10,
                         node_id="edge-1-medium")
    node = cluster.nodes["edge-1-medium"]
    assert node.task_count >= 10                  # work actually ran there
    assert node.mem_used_bytes > 0                # memory accounted there
    for other in ("edge-0-high", "edge-2-low"):
        assert cluster.nodes[other].mem_used_bytes == 0


def test_run_monolithic_deployer_assignment_consistent(graph):
    cluster = make_paper_cluster()
    d = DistributedInference(cluster, ModelPartitioner(graph),
                             num_partitions=1, assignment=["edge-2-low"])
    assert d.deployer.assignment() == d.placement == {0: "edge-2-low"}


# --- oracle-free properties of the event loop ---------------------------------

#: each property holds for one stream alone and for three open-loop
#: tenants interleaved on the same fleet
STREAMS = ("single", "tenants3")


def _property_reports(graph, stream, arrivals=None, n=120, seed=0,
                      concurrency=6, **cfg_kw):
    """Per-stream reports of one event-loop run on a 6-node fleet.
    ``arrivals(i)`` gives tenant i's arrival process (``None`` keeps the
    single stream closed-loop); ``"tenants3"`` registers three tenants
    with seeds ``seed + i`` on one shared heap."""
    cluster = make_synthetic_cluster(6, seed=3)
    cfg = EngineConfig(**cfg_kw)
    if stream == "single":
        pipe = DistributedInference(cluster, ModelPartitioner(graph),
                                    num_partitions=3, method="planner")
        return [pipe.run(n, repeat_rate=0.2, seed=seed,
                         concurrency=concurrency, engine=cfg,
                         arrivals=arrivals(0) if arrivals else None)]
    reg = TenantRegistry(cluster)
    for i in range(3):
        reg.add(f"t{i}", ModelPartitioner(graph),
                traffic=TenantTraffic(num_requests=n, repeat_rate=0.2,
                                      seed=seed + i, concurrency=concurrency,
                                      arrivals=arrivals(i)),
                num_partitions=3, method="planner")
    return list(reg.run(engine=cfg).reports.values())


@pytest.mark.parametrize("stream", STREAMS)
def test_request_conservation(graph, stream):
    """Every request finishes, after its submit, which follows its
    arrival; every request runs every stage once (batch histogram)."""
    arrivals = (None if stream == "single" else
                lambda i: PoissonArrivals(rate_rps=3.0, seed=7 + i))
    for rep in _property_reports(graph, stream, arrivals, n=150,
                                 micro_batch=4, adaptive_batch=True,
                                 transfer="overlap"):
        c = rep.columns
        assert len(c) == 150
        assert np.all(c.finish_ms > 0)              # every request finished
        assert np.all(c.finish_ms >= c.submit_ms)
        assert np.all(c.submit_ms >= c.arrival_ms)
        assert sum(k * v for k, v in rep.batch_hist.items()) % 150 == 0


@pytest.mark.parametrize("stream", STREAMS)
def test_per_node_fifo_order(graph, stream):
    """k=1 FIFO queues: each stream's requests leave each stage in
    submit order, so finish times are non-decreasing in request index —
    also when other tenants' requests share the queues."""
    for rep in _property_reports(
            graph, stream, lambda i: DeterministicArrivals.at_rate(2.0),
            n=100):
        assert np.all(np.diff(rep.columns.finish_ms) >= 0)
        assert np.all(np.diff(rep.columns.submit_ms) >= 0)


@pytest.mark.parametrize("stream", STREAMS)
def test_goodput_not_above_offered_load(graph, stream):
    """Completions per simulated second cannot exceed the offered arrival
    rate: the makespan extends at least to the last arrival."""
    for rep in _property_reports(
            graph, stream,
            lambda i: PoissonArrivals(rate_rps=5.0, seed=11 + i), n=200):
        c = rep.columns
        makespan_s = (c.finish_ms.max() - c.arrival_ms.min()) / 1000.0
        goodput = len(c) / makespan_s
        offered = len(c) / ((c.arrival_ms.max() - c.arrival_ms.min())
                            / 1000.0)
        assert goodput <= offered * (1 + 1e-9)


def _scramble_global_rngs(seed: int) -> None:
    random.seed(seed)
    np.random.seed(seed % 1000)
    _ = [random.random() for _ in range(37)] + [np.random.random()]


@pytest.mark.parametrize("stream", STREAMS)
def test_determinism_under_global_rng_scrambling(graph, stream):
    """The engine draws randomness only from explicitly seeded
    generators: scrambling the global RNGs between runs changes nothing."""
    def run():
        return _property_reports(
            graph, stream,
            lambda i: PoissonArrivals(rate_rps=4.0, seed=2 + i), n=150,
            micro_batch=4, transfer="serial")

    _scramble_global_rngs(1234)
    a = run()
    _scramble_global_rngs(987654)
    b = run()
    for ra, rb in zip(a, b):
        assert ra.columns.bitwise_equal(rb.columns)
        assert ra.batch_hist == rb.batch_hist
        assert ra.network_bytes == rb.network_bytes


def _sliced_registry(graph, adaptive: bool):
    """Three tenants on disjoint 4-node slices of a 12-node fleet, open
    loop, with a CPU throttle on one of tenant 0's nodes at 2 s."""
    cluster = make_synthetic_cluster(12, seed=77)
    nids = list(cluster.nodes)
    reg = TenantRegistry(cluster)
    for i in range(3):
        reg.add(f"t{i}", ModelPartitioner(graph),
                traffic=TenantTraffic(
                    num_requests=60, seed=5 + i, concurrency=8,
                    arrivals=PoissonArrivals(rate_rps=8.0, seed=11 + i)),
                num_partitions=3, method="planner", adaptive=adaptive,
                nodes=nids[4 * i:4 * i + 4])
    victim = reg.tenants["t0"].pipeline.placement[0]
    return reg, [cpu_throttle(2000.0, victim, cpu=0.2)]


@pytest.mark.parametrize("adaptive", (False, True),
                         ids=("free-running", "adaptive"))
def test_interleaved_tenants_are_deterministic(graph, adaptive):
    """Three tenants interleaved on one heap, with or without adaptation
    controllers (and their arbiter), reproduce every report field on a
    repeat run from fresh state: columns, batch histograms, queue-depth
    series, adaptation logs, monitor overhead and the arbitration
    summary. Every placement stays inside its tenant's ``nodes=`` slice,
    migrations included."""
    def run():
        reg, scenario = _sliced_registry(graph, adaptive)
        res = reg.run(scenario=scenario,
                      engine=EngineConfig(transfer="overlap", micro_batch=4,
                                          adaptive_batch=True))
        for t in reg.tenants.values():
            assert set(t.placement.values()) <= t.pipeline.allowed_nodes
        return res

    _scramble_global_rngs(4321)
    a = run()
    _scramble_global_rngs(8765)
    b = run()
    assert set(a.reports) == set(b.reports) == {"t0", "t1", "t2"}
    for name, ra in a.reports.items():
        rb = b.reports[name]
        assert ra.columns.bitwise_equal(rb.columns), name
        assert ra.batch_hist == rb.batch_hist, name
        assert ra.network_bytes == rb.network_bytes, name
        assert np.array_equal(ra.queue_depth[0], rb.queue_depth[0]), name
        assert np.array_equal(ra.queue_depth[1], rb.queue_depth[1]), name
        assert ra.adaptation == rb.adaptation, name
        assert ra.monitor_overhead_pct == rb.monitor_overhead_pct, name
        assert ra.stability == rb.stability, name
        assert (ra.adaptation is not None) == adaptive, name
    assert a.arbitration == b.arbitration
    if adaptive:   # the throttle made tenant 0's controller migrate
        assert a.reports["t0"].adaptation["migrations"] >= 1
