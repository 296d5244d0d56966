"""Open-loop traffic subsystem: generative engine invariants, degenerate
parity with the closed loop, shared-fabric parity, explicit-RNG isolation,
SLO metrics, adaptive micro-batching, and the overload drift trigger.

The generative suite (``test_generative_*``) samples >200 configurations
(cluster shape x arrival process x transfer model x fabric x micro-batch x
seed) through ``hypothesis`` and asserts *structural* invariants rather than pinned numbers — the contract
every future engine change must keep.
"""

import random

import numpy as np
import pytest

from conftest import given, settings, st
from repro.core.adaptation import jitter_events, node_death, node_recovery
from repro.core.cluster import make_paper_cluster, make_synthetic_cluster
from repro.core.engine import EngineConfig
from repro.core.partitioner import ModelPartitioner
from repro.core.pipeline import DistributedInference, RequestColumns, RunReport
from repro.core.traffic import (ADAPTIVE_BATCH_STEP, BurstyArrivals,
                                DeterministicArrivals, PoissonArrivals,
                                TraceArrivals, adaptive_k)
from repro.models.graph import LayerSpec, ModelGraph

COLUMNS = ("submit_ms", "finish_ms", "comm_ms", "service_ms",
           "cache_hits", "stages", "arrival_ms")

#: engine-result columns for open-loop vs closed-loop parity: arrival_ms is
#: traffic metadata (t0 for the degenerate burst, == submit in closed loop)
#: and legitimately differs between the two submission modes
PARITY_COLUMNS = tuple(f for f in COLUMNS if f != "arrival_ms")

#: explicit stage->node assignment where the bottleneck (0.4-CPU) stage
#: sends a boundary (same as tests/test_engine.py)
BOTTLENECK_SENDS = ["edge-2-low", "edge-0-high", "edge-1-medium"]


def tiny_graph(n_layers: int, seed: int) -> ModelGraph:
    """A small deterministic layer chain (no RNG): costs and boundary sizes
    vary with ``seed`` so sampled configs exercise unbalanced pipelines."""
    layers = [
        LayerSpec(name=f"l{i}", kind="Linear",
                  params=10_000 * (1 + (seed + i) % 3),
                  cost=2e5 * (1 + (seed + 2 * i) % 5),
                  out_bytes=30_000 * (1 + (seed + i) % 4))
        for i in range(n_layers)]
    return ModelGraph(f"tiny-{n_layers}-{seed}", layers)


def _arrival_process(kind: int, gap_ms: float, seed: int):
    if kind == 0:
        return DeterministicArrivals(gap_ms)
    if kind == 1:
        return PoissonArrivals(rate_rps=1000.0 / max(gap_ms, 1.0), seed=seed)
    if kind == 2:
        # dwell means floored like the rate: bursts far shorter than the
        # arrival gap (gap_ms near 0) emit almost nothing, and the on/off
        # walk then takes unboundedly many dwells to produce n_req arrivals
        return BurstyArrivals(on_rate_rps=2000.0 / max(gap_ms, 1.0),
                              mean_on_ms=5 * max(gap_ms, 1.0),
                              mean_off_ms=5 * max(gap_ms, 1.0), seed=seed)
    base = DeterministicArrivals(gap_ms).offsets(8)     # short trace, looped
    return TraceArrivals(base + (seed % 7))


def _openloop_run(nodes, layers, proc_kind, gap_ms, transfer, fabric, k,
                  adaptive, seed, n_req=28, use_cache=False, repeat=0.0):
    cluster = make_synthetic_cluster(nodes, seed=seed)
    d = DistributedInference(cluster, ModelPartitioner(tiny_graph(layers, seed)),
                             num_partitions=min(nodes, layers),
                             use_cache=use_cache)
    cfg = EngineConfig(transfer=transfer, micro_batch=k, fabric=fabric,
                       adaptive_batch=adaptive)
    rep = d.run(n_req, arrivals=_arrival_process(proc_kind, gap_ms, seed),
                engine=cfg, concurrency=8, seed=seed, repeat_rate=repeat)
    # conservation's flip side: a drained run leaves no per-node backlog
    assert all(n.queue_depth == 0 for n in d.cluster.nodes.values()), \
        "engine left residual per-node backlog after drain"
    return rep


def _assert_invariants(rep: RunReport, fifo: bool = True):
    c = rep.columns
    # event-time monotonicity + causality
    assert bool(np.all(np.diff(c.arrival_ms) >= 0)), "arrivals out of order"
    assert bool(np.all(c.submit_ms >= c.arrival_ms)), "admitted before arrival"
    assert bool(np.all(c.finish_ms >= c.submit_ms)), "finished before submit"
    # conservation: the engine raises if it drains with requests in flight,
    # so a returned report means arrivals == completions; every row is real
    assert bool(np.all(c.finish_ms > 0.0))
    # per-node FIFO: all requests traverse the same stage chain, every queue
    # is FIFO, and batches finish together -> completion order == admission
    # order. Callers relax this when overtaking is legitimate: cache-hit
    # chains skip stages, and fair-shared links let a small flow finish
    # before a bigger earlier one (processor sharing is not FIFO across
    # unequal micro-batch sizes)
    if fifo:
        assert bool(np.all(np.diff(c.finish_ms) >= 0)), "FIFO order violated"
    # goodput can never exceed offered load (for any deadline)
    assert rep.goodput_rps(float("inf")) <= rep.offered_load_rps + 1e-9
    assert rep.goodput_rps(500.0) <= rep.goodput_rps(float("inf")) + 1e-9
    # queue-depth series: poll-tick samples, monotone time, non-negative
    qt, qn = rep.queue_depth
    assert bool(np.all(np.diff(qt) >= 0)) and bool(np.all(qn >= 0))


def _assert_bitwise_equal(rep_a: RunReport, rep_b: RunReport):
    for f in COLUMNS:
        a, b = getattr(rep_a.columns, f), getattr(rep_b.columns, f)
        assert np.array_equal(a, b), (
            f"column {f} diverges at requests "
            f"{np.flatnonzero(a != b)[:5].tolist()}")
    assert rep_a.network_bytes == rep_b.network_bytes
    qa, qb = rep_a.queue_depth, rep_b.queue_depth
    assert np.array_equal(qa[0], qb[0]) and np.array_equal(qa[1], qb[1])


# --- generative engine-invariant suite ---------------------------------------

@settings(max_examples=120, deadline=None)
@given(nodes=st.integers(2, 4), layers=st.integers(4, 8),
       proc_kind=st.integers(0, 3), gap_ms=st.floats(0.0, 400.0),
       transfer=st.integers(0, 2), fabric=st.integers(0, 1),
       k=st.integers(1, 4), adaptive=st.integers(0, 1),
       seed=st.integers(0, 10_000))
def test_generative_openloop_invariants(nodes, layers, proc_kind, gap_ms,
                                        transfer, fabric, k, adaptive, seed):
    """Structural invariants + bit-for-bit determinism across randomized
    (cluster, arrival process, transfer model, fabric, micro-batch, seed)
    configurations: two runs from identical fresh state must agree on every
    metric column, and each run must satisfy monotonicity, conservation,
    FIFO completion order, and goodput <= offered load."""
    args = (nodes, layers, proc_kind, gap_ms,
            ("legacy", "serial", "overlap")[transfer],
            ("isolated", "shared")[fabric], k, bool(adaptive), seed)
    rep_a = _openloop_run(*args)
    rep_b = _openloop_run(*args)
    # fair-shared links + micro-batching may legitimately reorder
    # completions (unequal flow sizes under processor sharing)
    _assert_invariants(rep_a, fifo=not (fabric == 1 and k > 1))
    _assert_bitwise_equal(rep_a, rep_b)


@settings(max_examples=60, deadline=None)
@given(proc_kind=st.integers(0, 3), gap_ms=st.floats(5.0, 200.0),
       k=st.integers(1, 4), seed=st.integers(0, 10_000))
def test_generative_cached_stream_invariants(proc_kind, gap_ms, k, seed):
    """The cache lets later requests overtake earlier ones (hit chains skip
    stages), so the FIFO invariant is relaxed — everything else, including
    bit determinism of the cache-hit columns, must still hold."""
    args = (3, 6, proc_kind, gap_ms, "overlap", "isolated", k, False, seed)
    rep_a = _openloop_run(*args, use_cache=True, repeat=0.6)
    rep_b = _openloop_run(*args, use_cache=True, repeat=0.6)
    _assert_invariants(rep_a, fifo=False)
    _assert_bitwise_equal(rep_a, rep_b)
    assert int(rep_a.columns.cache_hits.sum()) >= 0


@settings(max_examples=40, deadline=None)
@given(gap_ms=st.floats(0.0, 60.0), k=st.integers(1, 4),
       seed=st.integers(0, 10_000))
def test_generative_shared_fabric_contention(gap_ms, k, seed):
    """Choked links force concurrent flows: the fair-sharing fabric must
    keep every structural invariant while actually splitting bandwidth
    (fabric telemetry is part of the determinism contract too)."""
    def run_once():
        cluster = make_paper_cluster()
        for nid in cluster.nodes:
            cluster.set_profile(nid, net_bw_mbps=2.0)
        d = DistributedInference(cluster, ModelPartitioner(tiny_graph(6, seed)),
                                 num_partitions=3)
        return d.run(24, arrivals=PoissonArrivals(
                         rate_rps=1000.0 / max(gap_ms, 2.0), seed=seed),
                     engine=EngineConfig(transfer="overlap", micro_batch=k,
                                         fabric="shared"),
                     concurrency=8, seed=seed)
    rep_a, rep_b = run_once(), run_once()
    # k > 1: unequal flow sizes on a fair-shared link may overtake (PS
    # scheduling); equal-size flows (k == 1) must still complete in order
    _assert_invariants(rep_a, fifo=(k == 1))
    _assert_bitwise_equal(rep_a, rep_b)
    fs = rep_a.fabric_stats
    assert fs == rep_b.fabric_stats
    assert fs["flows"] >= 1 and fs["shared_flows"] <= fs["flows"]
    assert fs["peak_concurrent"] >= 1


# --- degenerate-case parity (bit-for-bit) ------------------------------------

@pytest.fixture(scope="module")
def graph():
    from repro.models.graph import mobilenetv2_graph
    return mobilenetv2_graph()


def _fresh(graph, **kw):
    return DistributedInference(make_paper_cluster(), ModelPartitioner(graph),
                                **kw)


@pytest.mark.parametrize("cfg", [
    EngineConfig(transfer="serial"),
    EngineConfig(transfer="overlap"),
    EngineConfig(transfer="overlap", micro_batch=4),
    EngineConfig(transfer="serial", fabric="shared"),
    EngineConfig(transfer="overlap", fabric="shared"),
], ids=["serial", "overlap", "overlap+mb4", "serial+sharedfab",
        "overlap+sharedfab"])
def test_zero_interarrival_matches_closed_loop(graph, cfg):
    """The degenerate open-loop stream — every request arrives at t0, the
    admission window meters them in — must reproduce the closed-loop
    engine's per-request results **bit-for-bit** (the closed loop is
    exactly 'W in flight, next enters when one finishes')."""
    closed = _fresh(graph).run(60, concurrency=8, engine=cfg)
    openl = _fresh(graph).run(60, concurrency=8, engine=cfg,
                              arrivals=DeterministicArrivals(0.0))
    for f in PARITY_COLUMNS:
        a, b = getattr(closed.columns, f), getattr(openl.columns, f)
        assert np.array_equal(a, b), f"column {f} diverges"
    assert closed.network_bytes == openl.network_bytes
    # the open-loop view additionally knows all requests arrived at t0
    assert float(openl.columns.arrival_ms.max()) == float(
        openl.columns.arrival_ms.min())


def test_shared_fabric_single_flow_matches_isolated(graph):
    """`serial` transfers under the shared fabric never put two flows on
    one link (the sender blocks until delivery), so fair sharing must
    degrade to the isolated per-link charge bit-for-bit — even on choked
    links where sharing would bite if it ever happened."""
    def run_once(fabric):
        cluster = make_paper_cluster()
        for nid in cluster.nodes:
            cluster.set_profile(nid, net_bw_mbps=2.0)
        d = DistributedInference(cluster, ModelPartitioner(graph),
                                 num_partitions=3,
                                 assignment=list(BOTTLENECK_SENDS))
        return d.run(60, engine=EngineConfig(transfer="serial",
                                             fabric=fabric))
    iso, shared = run_once("isolated"), run_once("shared")
    for f in COLUMNS:
        assert np.array_equal(getattr(iso.columns, f),
                              getattr(shared.columns, f)), f
    assert shared.fabric_stats["peak_concurrent"] == 1
    assert shared.fabric_stats["shared_flows"] == 0


def test_shared_fabric_window1_matches_isolated(graph):
    """With one request in flight, overlap-mode transfers can never
    overlap either — the second solo-flow degenerate case."""
    iso = _fresh(graph).run(40, concurrency=1,
                            engine=EngineConfig(transfer="overlap"))
    shared = _fresh(graph).run(40, concurrency=1,
                               engine=EngineConfig(transfer="overlap",
                                                   fabric="shared"))
    for f in COLUMNS:
        assert np.array_equal(getattr(iso.columns, f),
                              getattr(shared.columns, f)), f


def test_shared_fabric_keeps_sender_tx_serialization(graph):
    """A node hosting two stages emits back-to-back sends to different
    receivers: the shared fabric must still queue them on the sender's tx
    link (regression: dropping the tx FIFO let one NIC transmit several
    flows at full rate in parallel, making "shared" MORE optimistic than
    the isolated charge). With receiver links uncontended, overlap+shared
    is then bit-for-bit equal to overlap+isolated even under tx queueing."""
    def run_once(fabric):
        d = DistributedInference(
            make_paper_cluster(), ModelPartitioner(graph), num_partitions=3,
            # stage 0 and 1 both on edge-0-high: consecutive boundary sends
            # from one NIC to two different receivers
            assignment=["edge-0-high", "edge-0-high", "edge-1-medium"])
        return d.run(60, engine=EngineConfig(transfer="overlap",
                                             fabric=fabric))
    iso, shared = run_once("isolated"), run_once("shared")
    for f in COLUMNS:
        assert np.array_equal(getattr(iso.columns, f),
                              getattr(shared.columns, f)), f


# --- max-min fabric: per-sender uplinks ---------------------------------------

@settings(max_examples=40, deadline=None)
@given(nflows=st.integers(min_value=1, max_value=8),
       nnodes=st.integers(min_value=2, max_value=5),
       seed=st.integers(min_value=0, max_value=10_000))
def test_maxmin_rates_properties(nflows, nnodes, seed):
    """The progressive-filling allocator satisfies the defining max-min
    properties on sampled topologies: non-negative rates, no link over
    capacity, and every flow bottlenecked at some saturated link where no
    co-resident flow gets a higher rate."""
    from repro.core.fabric import maxmin_rates
    rnd = random.Random(seed)
    caps = {}
    flows = []
    for i in range(nflows):
        tx = f"tx:n{rnd.randrange(nnodes)}"
        rx = f"rx:n{rnd.randrange(nnodes)}"
        for link in (tx, rx):
            caps.setdefault(link, rnd.choice([1.0, 2.0, 5.0, 10.0]))
        flows.append((tx, rx))
    rates = maxmin_rates(flows, caps)
    assert all(r >= 0.0 for r in rates)
    load = {}
    for links, r in zip(flows, rates):
        for link in links:
            load[link] = load.get(link, 0.0) + r
    for link, used in load.items():
        assert used <= caps[link] + 1e-9, f"{link} over capacity"
    # max-min certificate: each flow saturates some link where its rate
    # is maximal among that link's flows
    for i, links in enumerate(flows):
        ok = False
        for link in links:
            saturated = load[link] >= caps[link] - 1e-9
            is_max = all(rates[j] <= rates[i] + 1e-9
                         for j, lj in enumerate(flows) if link in lj)
            if saturated and is_max:
                ok = True
        assert ok, f"flow {i} not max-min bottlenecked"


def test_maxmin_solo_flow_matches_isolated(graph):
    """The dual-endpoint fabric keeps the solo-flow guarantee: a run in
    which no two flows ever overlap on either endpoint is bit-for-bit
    the isolated accounting (window-1 closed loop can never overlap)."""
    iso = _fresh(graph).run(40, concurrency=1,
                            engine=EngineConfig(transfer="overlap"))
    mm = _fresh(graph).run(40, concurrency=1,
                           engine=EngineConfig(transfer="overlap",
                                               fabric="maxmin"))
    for f in COLUMNS:
        assert np.array_equal(getattr(iso.columns, f),
                              getattr(mm.columns, f)), f
    assert mm.fabric_stats["shared_flows"] == 0


def test_maxmin_uplink_throttles_fanout(graph):
    """A node hosting two stages fans out to two receivers: under
    receiver-only sharing its sends queue on the tx FIFO; under max-min
    they run concurrently but split the sender's uplink. Choking the
    sender's uplink must slow delivery vs. an unconstrained one —
    the contention the receiver-only model cannot express."""
    def run_once(sender_bw):
        cluster = make_paper_cluster()
        cluster.set_profile("edge-0-high", net_bw_mbps=sender_bw)
        d = DistributedInference(
            cluster, ModelPartitioner(graph), num_partitions=3,
            assignment=["edge-0-high", "edge-0-high", "edge-1-medium"])
        return d.run(60, engine=EngineConfig(transfer="overlap",
                                             fabric="maxmin"))
    slow = run_once(2.0)       # choked uplink: concurrent sends split 2 Mbps
    fast = run_once(800.0)
    assert slow.fabric_stats["shared_flows"] > 0
    assert (slow.tail_throughput_rps() < fast.tail_throughput_rps())


def test_maxmin_solo_slow_uplink_uses_fluid_accounting():
    """Regression: a SOLO flow behind a sender uplink slower than its
    receiver downlink must fall to fluid (uplink-bound) accounting — the
    receiver-based solo time would stamp delivery before the event that
    releases it and hide the uplink wait from sojourn entirely."""
    from repro.core.fabric import FairShareFabric
    f = FairShareFabric(shared_uplinks=True)
    # 1000 bits, receiver drains 100 bits/ms (solo_ms = 1 + 10), but the
    # sender's uplink only drains 1 bit/ms -> true wire time ~1000 ms
    ver, nxt = f.start("rx-node", 100.0, 1000.0, 11.0, 1.0, "payload", 0.0,
                       sender_id="tx-node", sender_rate=1.0)
    assert nxt == pytest.approx(1000.0)          # uplink-bound completion
    delivered, _ = f.on_event("rx-node", ver, nxt)
    (payload, at, elapsed), = delivered
    assert payload == "payload"
    assert at >= nxt                             # never delivered in the past
    assert at == pytest.approx(1001.0)           # bw completion + latency
    assert elapsed == pytest.approx(1001.0)
    assert f.stats()["shared_flows"] == 1        # left the isolated path


def test_maxmin_uplink_shared_but_downlink_bound_keeps_parity():
    """The complement: two flows share a sender uplink wide enough that
    each still gets its full receiver rate — isolated accounting remains
    exactly correct, so neither flow is disturbed."""
    from repro.core.fabric import FairShareFabric
    f = FairShareFabric(shared_uplinks=True)
    # uplink 200 bits/ms shared by two flows; each receiver takes 100
    v1, _ = f.start("rx-a", 100.0, 1000.0, 11.0, 1.0, "p1", 0.0,
                    sender_id="tx", sender_rate=200.0)
    v2, nxt = f.start("rx-b", 100.0, 1000.0, 11.0, 1.0, "p2", 0.0,
                      sender_id="tx", sender_rate=200.0)
    delivered, _ = f.on_event("rx-b", v2, nxt)
    assert all(at == pytest.approx(11.0) and el == pytest.approx(11.0)
               for _, at, el in delivered)       # exact solo accounting
    assert f.stats()["shared_flows"] == 0


def test_maxmin_conservation_and_determinism(graph):
    """Max-min runs drain fully and are bit-reproducible."""
    def run_once():
        d = _fresh(graph, num_partitions=3,
                   assignment=list(BOTTLENECK_SENDS))
        rep = d.run(50, engine=EngineConfig(transfer="overlap",
                                            micro_batch=3, fabric="maxmin"),
                    arrivals=PoissonArrivals(rate_rps=3.0, seed=5))
        assert all(n.queue_depth == 0 for n in d.cluster.nodes.values())
        return rep
    rep1 = run_once()
    np.random.seed(99)
    rep2 = run_once()
    for f in COLUMNS:
        assert np.array_equal(getattr(rep1.columns, f),
                              getattr(rep2.columns, f)), f


# --- per-stage adaptive micro-batch -------------------------------------------

def test_adaptive_batch_light_load_equals_unbatched(graph):
    """Satellite regression: under light open-loop load the per-STAGE
    backlog never reaches the adaptive step, so every batch is size 1 and
    the run is bit-for-bit the micro_batch=1 run — head-of-batch latency
    is exactly the unbatched latency, never inflated by amortization the
    load didn't need."""
    light = PoissonArrivals(rate_rps=0.5, seed=7)
    adaptive = _fresh(graph).run(
        60, arrivals=light,
        engine=EngineConfig(transfer="overlap", micro_batch=8,
                            adaptive_batch=True))
    unbatched = _fresh(graph).run(
        60, arrivals=light, engine=EngineConfig(transfer="overlap"))
    assert set(adaptive.batch_hist) == {1}, adaptive.batch_hist
    for f in COLUMNS:
        assert np.array_equal(getattr(adaptive.columns, f),
                              getattr(unbatched.columns, f)), f


def test_adaptive_batch_counts_per_stage_backlog(graph):
    """The adaptive cap follows the served stage's own backlog, not the
    node's total queue: another tenant's standing backlog on the same
    node must not unlock deep batches for a lightly-loaded stage."""
    from repro.core.engine import MultiTenantEngine
    from repro.core.tenancy import Tenant, TenantTraffic
    from repro.core.cluster import make_paper_cluster as _mpc

    def tenants(cluster):
        heavy = Tenant("heavy", traffic=TenantTraffic(
            num_requests=80, concurrency=64,
            arrivals=DeterministicArrivals(0.0)))   # burst: deep backlog
        light = Tenant("light", traffic=TenantTraffic(
            num_requests=12, concurrency=2,
            arrivals=DeterministicArrivals.at_rate(0.5)))
        for t in (heavy, light):
            DistributedInference(cluster, ModelPartitioner(graph),
                                 num_partitions=1,
                                 assignment=["edge-0-high"], tenant=t)
        return [heavy, light]

    cluster = _mpc()
    reps = MultiTenantEngine(cluster, tenants(cluster)).run(
        config=EngineConfig(transfer="overlap", micro_batch=8,
                            adaptive_batch=True))
    # the bursty tenant amortizes; the light tenant's head-of-batch
    # latency stays bounded: its batches never grow past its own backlog
    assert max(reps["heavy"].batch_hist) > 1
    assert max(reps["light"].batch_hist) <= 2, reps["light"].batch_hist


# --- explicit-RNG isolation ---------------------------------------------------

def test_no_global_rng_dependence(graph):
    """Scrambling the global NumPy + Python RNG state between two identical
    runs must not change a single bit of the report: every stochastic
    component (arrival processes, request signatures, scenario jitter)
    threads its own seeded Generator."""
    def run_once():
        d = _fresh(graph, use_cache=True)
        jrng = np.random.default_rng(42)
        scenario = jitter_events(
            [node_death(1e12, "edge-2-low")], jrng)   # never fires; jittered
        return d.run(50, repeat_rate=0.5, seed=7, scenario=scenario,
                     arrivals=PoissonArrivals(rate_rps=2.0, seed=9),
                     engine=EngineConfig(transfer="overlap", micro_batch=2))
    np.random.seed(12345)
    random.seed(54321)
    rep_a = run_once()
    np.random.seed(999)
    random.seed(111)
    rep_b = run_once()
    _assert_bitwise_equal(rep_a, rep_b)
    assert rep_a.cache_stats == rep_b.cache_stats


def test_shared_fabric_sees_midrun_bandwidth_throttle(graph):
    """A ScenarioEvent throttling a receiver's bandwidth must reach links
    the fabric already created: flows started after the throttle drain at
    the new rate (regression: `_Link.rate` was frozen at creation)."""
    from repro.core.adaptation import ScenarioEvent

    def run_once(throttle: bool):
        cluster = make_paper_cluster()
        for nid in cluster.nodes:
            cluster.set_profile(nid, net_bw_mbps=50.0)
        d = DistributedInference(cluster, ModelPartitioner(graph),
                                 num_partitions=3,
                                 assignment=list(BOTTLENECK_SENDS))
        scenario = ([ScenarioEvent(500.0, "profile", "edge-0-high",
                                   dict(net_bw_mbps=2.0))]
                    if throttle else None)
        return d.run(60, scenario=scenario,
                     engine=EngineConfig(transfer="overlap",
                                         fabric="shared"))
    plain = run_once(False)
    throttled = run_once(True)
    assert (float(throttled.columns.finish_ms.max())
            > float(plain.columns.finish_ms.max())), \
        "mid-run bandwidth throttle had no effect on the shared fabric"


def test_jitter_events_preserves_original_order():
    """Dependent pairs (death then recovery of one node) must never swap,
    even when their jitter windows overlap (regression: independent jitter
    + re-sort turned transient outages into permanent ones)."""
    evs = [node_death(100.0, "n"), node_recovery(120.0, "n")]
    for s in range(50):
        j = jitter_events(evs, np.random.default_rng(s), max_jitter_ms=80.0)
        assert [e.action for e in j] == ["offline", "recover"]
        assert j[0].at_ms <= j[1].at_ms


def test_jitter_events_explicit_generator():
    """jitter_events draws only from the caller's Generator: same seed ->
    same jitter, different seed -> different jitter, global state
    irrelevant; times stay non-negative and sorted."""
    evs = [node_death(50.0, "a"), node_death(10.0, "b"), node_death(0.0, "c")]
    j1 = jitter_events(evs, np.random.default_rng(3), max_jitter_ms=30.0)
    j2 = jitter_events(evs, np.random.default_rng(3), max_jitter_ms=30.0)
    j3 = jitter_events(evs, np.random.default_rng(4), max_jitter_ms=30.0)
    assert [e.at_ms for e in j1] == [e.at_ms for e in j2]
    assert [e.at_ms for e in j1] != [e.at_ms for e in j3]
    assert all(e.at_ms >= 0.0 for e in j1)
    assert [e.at_ms for e in j1] == sorted(e.at_ms for e in j1)
    assert {e.node_id for e in j1} == {"a", "b", "c"}


# --- arrival processes --------------------------------------------------------

def test_deterministic_offsets_and_rate():
    p = DeterministicArrivals.at_rate(4.0)
    offs = p.offsets(5)
    np.testing.assert_allclose(offs, [0.0, 250.0, 500.0, 750.0, 1000.0])
    assert DeterministicArrivals(0.0).offsets(3).tolist() == [0.0, 0.0, 0.0]


def test_poisson_offsets_mean_and_purity():
    p = PoissonArrivals(rate_rps=10.0, seed=5)
    offs = p.offsets(4000)
    gaps = np.diff(np.concatenate([[0.0], offs]))
    assert abs(float(gaps.mean()) - 100.0) < 10.0     # ~100 ms mean gap
    np.testing.assert_array_equal(offs, p.offsets(4000))   # pure


def test_bursty_is_burstier_than_poisson():
    """MMPP on/off gaps must have a higher coefficient of variation than
    the exponential (CV=1) at matched mean rate — the defining property."""
    b = BurstyArrivals(on_rate_rps=20.0, off_rate_rps=0.0,
                       mean_on_ms=500.0, mean_off_ms=500.0, seed=2)
    offs = b.offsets(3000)
    gaps = np.diff(offs)
    cv = float(gaps.std() / gaps.mean())
    assert cv > 1.3, f"CV {cv} not bursty"
    assert bool(np.all(gaps >= 0))


def test_bursty_refuses_zero_length_bursts():
    """Bursts of zero mean length emit nothing; offsets() must refuse them
    instead of walking the on/off chain forever."""
    b = BurstyArrivals(on_rate_rps=2000.0, mean_on_ms=0.0, mean_off_ms=0.0)
    with pytest.raises(AssertionError):
        b.offsets(4)


def test_trace_arrivals_file_roundtrip(tmp_path):
    f = tmp_path / "trace.txt"
    f.write_text("# recorded arrivals (ms)\n100.0\n\n150.0\n400.0\n")
    tr = TraceArrivals.from_file(f)
    assert len(tr) == 3
    np.testing.assert_allclose(tr.offsets(3), [0.0, 50.0, 300.0])


def test_trace_arrivals_loop_replay():
    tr = TraceArrivals([0.0, 10.0, 30.0])
    offs = tr.offsets(7)
    assert len(offs) == 7
    assert bool(np.all(np.diff(offs) > 0))            # wrap adds the mean gap
    np.testing.assert_allclose(offs[:3], [0.0, 10.0, 30.0])
    np.testing.assert_allclose(offs[3:6], np.array([0.0, 10.0, 30.0]) + 45.0)


def test_trace_arrivals_zero_span_loop_regression():
    """A multi-entry trace of identical timestamps has span 0, so the
    mean gap is 0 — the wrap must still advance each repetition (by the
    positive fallback gap) instead of replaying every loop at the same
    instant (the double-arrival the shift exists to avoid)."""
    tr = TraceArrivals([5.0, 5.0, 5.0])
    offs = tr.offsets(8)
    assert len(offs) == 8
    assert bool(np.all(np.diff(offs) >= 0))
    # arrivals within one repetition are legitimately simultaneous...
    np.testing.assert_allclose(offs[:3], 0.0)
    # ...but each repetition starts strictly later than the last
    np.testing.assert_allclose(offs[3:6], 1.0)
    np.testing.assert_allclose(offs[6:], 2.0)
    # the single-entry trace keeps its 1.0 ms fallback gap
    np.testing.assert_allclose(TraceArrivals([7.0]).offsets(3),
                               [0.0, 1.0, 2.0])


# --- SLO metrics --------------------------------------------------------------

def test_slo_metrics_exact():
    cols = RequestColumns(4)
    cols.arrival_ms[:] = [0.0, 100.0, 200.0, 300.0]
    cols.submit_ms[:] = [0.0, 100.0, 250.0, 400.0]
    cols.finish_ms[:] = [50.0, 500.0, 450.0, 1300.0]
    rep = RunReport("slo", columns=cols)
    np.testing.assert_allclose(rep.columns.sojourn_ms,
                               [50.0, 400.0, 250.0, 1000.0])
    assert rep.columns.deadline_met(400.0).tolist() == [True, True, True, False]
    assert rep.deadline_hit_rate(400.0) == pytest.approx(0.75)
    # offered: 4 arrivals over 300 ms; goodput(400ms): 3 hits over 1300 ms
    assert rep.offered_load_rps == pytest.approx(4000.0 / 300.0)
    assert rep.goodput_rps(400.0) == pytest.approx(3000.0 / 1300.0)
    assert rep.p50_sojourn_ms == 400.0      # sorted[2] by the index convention
    assert rep.p99_sojourn_ms == 1000.0
    assert rep.p999_sojourn_ms == 1000.0


def test_queue_depth_grows_under_overload(graph):
    light = _fresh(graph).run(
        80, arrivals=PoissonArrivals(rate_rps=1.0, seed=3),
        engine=EngineConfig(transfer="overlap"))
    heavy = _fresh(graph).run(
        80, arrivals=PoissonArrivals(rate_rps=6.0, seed=3),
        engine=EngineConfig(transfer="overlap"))
    assert int(heavy.queue_depth[1].max()) > int(light.queue_depth[1].max())
    assert heavy.p99_sojourn_ms > light.p99_sojourn_ms
    # under overload the goodput-vs-offered gap opens
    dl = 2000.0
    assert (heavy.offered_load_rps - heavy.goodput_rps(dl)
            > light.offered_load_rps - light.goodput_rps(dl))


# --- adaptive micro-batching --------------------------------------------------

def test_adaptive_k_rule():
    assert adaptive_k(0, 8) == 1
    assert adaptive_k(ADAPTIVE_BATCH_STEP - 1, 8) == 1
    assert adaptive_k(ADAPTIVE_BATCH_STEP, 8) == 2
    assert adaptive_k(100, 8) == 8                    # capped at max_k
    assert adaptive_k(100, 1) == 1
    ks = [adaptive_k(d, 8) for d in range(60)]
    assert ks == sorted(ks)                           # monotone in backlog


def test_adaptive_batching_tracks_backlog(graph):
    """Under a standing backlog the controller must actually grow batches
    (sizes > 1 appear) while still serving short queues in small batches
    (sizes < max appear) — visible in the batch histogram."""
    d = _fresh(graph, num_partitions=3, assignment=list(BOTTLENECK_SENDS))
    rep = d.run(120, concurrency=64,
                arrivals=DeterministicArrivals(0.0),   # burst of 120 at t0
                engine=EngineConfig(transfer="overlap", micro_batch=8,
                                    adaptive_batch=True))
    hist = rep.batch_hist
    assert max(hist) > 1, f"never batched: {hist}"
    assert min(hist) == 1, f"never served a short queue solo: {hist}"
    assert all(k <= 8 for k in hist)
    # amortization must beat unbatched on the same burst
    d1 = _fresh(graph, num_partitions=3, assignment=list(BOTTLENECK_SENDS))
    rep1 = d1.run(120, concurrency=64, arrivals=DeterministicArrivals(0.0),
                  engine=EngineConfig(transfer="overlap", micro_batch=1))
    assert rep.tail_throughput_rps() > rep1.tail_throughput_rps()


# --- overload drift trigger ---------------------------------------------------

def test_arrival_overload_drift_detected(graph):
    d = _fresh(graph, adaptive=True)
    d.run(150, arrivals=PoissonArrivals(rate_rps=8.0, seed=1),
          engine=EngineConfig(transfer="overlap"))
    drifts = [e for e in d.controller.events
              if e.kind == "drift" and e.detail == "arrival-overload"]
    assert drifts, "sustained offered >> completed must raise the drift"


def test_overload_drift_with_large_sustained_polls(graph):
    """sustained_polls beyond the old hard-coded 32-deep window must still
    fire the drift once enough consecutive overloaded polls accumulate
    (regression: deque(maxlen=32) silently disabled the trigger)."""
    from repro.core.adaptation import AdaptationConfig
    d = _fresh(graph, adaptation=AdaptationConfig(sustained_polls=40))
    # deterministic rate: every poll window sees exactly 5 arrivals, so the
    # overload run is strictly consecutive (a Poisson stream's occasional
    # zero-arrival window would reset the sustained counter)
    d.run(300, arrivals=DeterministicArrivals.at_rate(5.0),
          engine=EngineConfig(transfer="overlap"))
    drifts = [e for e in d.controller.events
              if e.kind == "drift" and e.detail == "arrival-overload"]
    assert drifts, "40 sustained overloaded polls must raise the drift"


def test_overload_observations_do_not_leak_into_legacy_run(graph):
    """A closed-loop stream can never be overloaded by construction: the
    legacy loop must reset rate observations at stream start, or a prior
    open-loop run's overload windows fire a spurious drift (regression)."""
    d = _fresh(graph, adaptive=True)
    d.run(120, arrivals=PoissonArrivals(rate_rps=8.0, seed=1),
          engine=EngineConfig(transfer="overlap"))
    before = len([e for e in d.controller.events
                  if e.detail == "arrival-overload"])
    assert before > 0
    d.run_legacy(30, concurrency=4)
    after = len([e for e in d.controller.events
                 if e.detail == "arrival-overload"])
    assert after == before, "stale overload windows leaked into run_legacy"


def test_no_overload_drift_under_light_load(graph):
    d = _fresh(graph, adaptive=True)
    d.run(60, arrivals=PoissonArrivals(rate_rps=1.0, seed=1),
          engine=EngineConfig(transfer="overlap"))
    drifts = [e for e in d.controller.events
              if e.kind == "drift" and e.detail == "arrival-overload"]
    assert not drifts, f"spurious overload drift: {drifts}"
