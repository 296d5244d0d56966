"""Pipeline-engine benchmark: 100k-request streams, transfer overlap,
micro-batching, open-loop traffic, multi-tenant serving, and a Table-I
drift guard.

The sections, written to ``BENCH_pipeline.json`` (repo root):

``table1``
    The paper's Table-I configurations (monolithic / AMP4EC / AMP4EC+Cache
    on the 3-node testbed) run through the event engine's default path,
    asserted **bit-for-bit equal** to the legacy loop — the proof that the
    engine refactor did not drift the reproduced model metrics.
``modes``
    Steady-state throughput of the four transfer/batching policies on the
    3-node testbed with the bottleneck stage sending a boundary: the naive
    blocking-send runtime (``serial``), the seed's optimistic accounting
    (``legacy``), DEFER-style overlap, and overlap + 4-way micro-batching.
``openloop``
    An offered-load sweep of Poisson open-loop traffic across the
    closed-loop capacity knee (~1.55 rps on the testbed): goodput, sojourn
    percentiles, deadline hit rate, and peak queue depth per transfer
    model. Shows where overlap + adaptive micro-batching sustains higher
    goodput than the blocking-send runtime once arrivals stop backing off.
``scale``
    A 100k-request stream on the 50-node synthetic cluster (DP-planner
    placement), through the fast parity path, the heap event path with
    overlap + 8-way micro-batching, and the same event path driven by a
    Poisson open-loop arrival process. Asserts the single-digit-second
    wall-time budget and reports simulated-requests-per-wall-second — the
    engine's figure of merit.
``batchcurve``
    The batch-aware planner vs the k=1 planner on a micro-batched
    open-loop stream. A graph with a large-activation front half and a
    fast-but-memory-tight node makes the two objectives disagree: the
    k=1 planner parks the heavy stage on the fast node (best per-item
    time), the batch-aware planner sees the k-scaled working set cross
    that node's memory at the operating micro-batch and routes around
    the thrash knee. Both plans run the identical overloaded open-loop
    stream; the batch-aware plan must win on predicted bottleneck *and*
    simulated goodput (asserted in-bench, pinned exactly). A final row
    prints the committed kernel-calibration artifact's predicted
    testbed bottleneck next to the analytic model's.
``dagsweep``
    Operator-DAG dataflow: an MoE-style branched plan (trunk → two
    asymmetric expert arms → join → tail) with a trunk early-exit head
    draining half the requests, reported with per-exit-head goodput, and
    a two-model cascade (cheap branched model escalating its exit misses
    into a MobileNetV2 tenant) against serving every request on the
    expensive model alone — the cascade must win on end-to-end goodput
    (asserted in-bench, committed numbers pinned exactly).
``multitenant``
    The tenancy layer at scale and under arbitration. (a) 3 tenants ×
    20 nodes × 10k open-loop requests each through one shared event heap
    (single-digit-second wall budget; aggregate + per-tenant goodput).
    (b) A shared-node throttle on a tight 10-node fleet over a slow
    fabric: cross-tenant arbitration with k-stage partial migrations vs
    per-tenant independent full re-planning — the arbitrated run must
    sustain strictly higher aggregate goodput (the committed numbers pin
    the win).

Run:  PYTHONPATH=src python benchmarks/pipeline_bench.py
"""

from __future__ import annotations

import json
import pathlib
import time
from typing import Optional

import numpy as np

from repro.core.cluster import make_paper_cluster, make_synthetic_cluster
from repro.core.cost_model import execution_ms_vec, working_set_bytes
from repro.core.engine import EngineConfig
from repro.core.partitioner import ModelPartitioner
from repro.core.pipeline import DistributedInference, run_monolithic
from repro.core.traffic import PoissonArrivals
from repro.models.graph import mobilenetv2_graph

OUT_PATH = pathlib.Path(__file__).resolve().parents[1] / "BENCH_pipeline.json"

#: 3-node assignment where the bottleneck (0.4-CPU) stage sends a boundary,
#: so blocking vs. overlapped transfers are distinguishable in steady state
BOTTLENECK_SENDS = ["edge-2-low", "edge-0-high", "edge-1-medium"]

TABLE1_REQUESTS = 60
MODE_REQUESTS = 400
SCALE_NODES = 50
SCALE_WALL_BUDGET_S = 10.0


def _columns_equal(a, b) -> bool:
    return all(np.array_equal(getattr(a.columns, f), getattr(b.columns, f))
               for f in ("submit_ms", "finish_ms", "comm_ms", "service_ms",
                         "cache_hits", "stages"))


def table1_rows():
    """Table-I configurations through the engine, with the legacy loop as
    the drift oracle (bit-for-bit assertion per configuration)."""
    g = mobilenetv2_graph()
    rows = []

    mono = run_monolithic(make_paper_cluster(("monolithic",)),
                          ModelPartitioner(g), TABLE1_REQUESTS)
    rows.append(mono.row())

    for name, kw, run_kw in (
            ("amp4ec", {}, {}),
            ("amp4ec+cache", dict(use_cache=True), dict(repeat_rate=0.8))):
        d_legacy = DistributedInference(make_paper_cluster(),
                                        ModelPartitioner(g), **kw)
        rep_legacy = d_legacy.run_legacy(TABLE1_REQUESTS, name=name, **run_kw)
        d_engine = DistributedInference(make_paper_cluster(),
                                        ModelPartitioner(g), **kw)
        rep_engine = d_engine.run(TABLE1_REQUESTS, name=name, **run_kw)
        assert _columns_equal(rep_legacy, rep_engine), (
            f"{name}: engine drifted from the legacy loop")
        row = rep_engine.row()
        row["matches_legacy_loop"] = True
        rows.append(row)
    return rows


def fresh_testbed(g):
    """The 3-node testbed pipeline every steady-state section benchmarks:
    3 partitions, bottleneck stage sending a boundary."""
    return DistributedInference(make_paper_cluster(), ModelPartitioner(g),
                                num_partitions=3,
                                assignment=list(BOTTLENECK_SENDS))


def mode_rows(num_requests: int = MODE_REQUESTS):
    """Steady-state comparison of the transfer/batching policies."""
    g = mobilenetv2_graph()

    def fresh():
        return fresh_testbed(g)

    configs = [
        ("serial-blocking-send", EngineConfig(transfer="serial")),
        ("legacy-accounting", None),
        ("overlap", EngineConfig(transfer="overlap")),
        ("overlap+microbatch4", EngineConfig(transfer="overlap",
                                             micro_batch=4)),
    ]
    rows = []
    tail = {}
    for name, cfg in configs:
        rep = fresh().run(num_requests, name=name, engine=cfg)
        tail[name] = rep.tail_throughput_rps()
        rows.append(dict(
            config=name,
            steady_state_ms=round(1000.0 / tail[name], 3),
            tail_throughput_rps=round(tail[name], 5),
            avg_latency_ms=round(rep.avg_latency_ms, 1),
            comm_overhead_ms=round(rep.avg_comm_ms, 2),
        ))
    assert tail["overlap"] > tail["serial-blocking-send"], \
        "overlapped transfer must beat the blocking-send runtime"
    assert tail["overlap+microbatch4"] > tail["legacy-accounting"], \
        "overlap + micro-batching must beat the legacy loop"

    # analytic micro-batch curve for the bottleneck stage from the
    # vectorized cost model: per-request steady time as k grows (the
    # amortization ceiling the simulated overlap+microbatch rows approach)
    d = fresh()
    bott = max(d.plan.partitions,
               key=lambda p: p.cost
               / d.cluster.nodes[d.placement[p.index]].profile.cpu)
    profile = d.cluster.nodes[d.placement[bott.index]].profile
    ks = np.arange(1, 9)
    ws = np.array([working_set_bytes(d.partitioner.graph, bott.lo, bott.hi,
                                     int(k)) for k in ks])
    curve = execution_ms_vec(bott.cost * ks, profile, ws) / ks
    rows.append(dict(
        config="predicted-bottleneck-microbatch-curve",
        # string keys: the committed baseline round-trips through JSON
        per_request_ms={str(int(k)): round(float(v), 3)
                        for k, v in zip(ks, curve)}))
    return rows


#: open-loop sweep: offered Poisson rates straddling the testbed's
#: closed-loop capacity (~1.55 rps with the bottleneck stage sending)
OPENLOOP_RATES = (1.2, 1.5, 1.8, 2.4)
OPENLOOP_REQUESTS = 400
OPENLOOP_DEADLINE_MS = 2000.0
OPENLOOP_SEED = 11


def openloop_rows(num_requests: int = OPENLOOP_REQUESTS):
    """Offered-load sweep: Poisson open-loop arrivals across the capacity
    knee, per transfer model. Bit-reproducible (seeded arrival process),
    so every field is guarded exactly by ``scripts/check_perf.py``."""
    g = mobilenetv2_graph()
    configs = [
        ("serial", EngineConfig(transfer="serial")),
        ("overlap+amb4", EngineConfig(transfer="overlap", micro_batch=4,
                                      fabric="shared", adaptive_batch=True)),
    ]
    rows = []
    goodput = {}
    for rate in OPENLOOP_RATES:
        for name, cfg in configs:
            d = fresh_testbed(g)
            rep = d.run(num_requests, name=name, engine=cfg,
                        arrivals=PoissonArrivals(rate_rps=rate,
                                                 seed=OPENLOOP_SEED))
            gp = rep.goodput_rps(OPENLOOP_DEADLINE_MS)
            assert gp <= rep.offered_load_rps + 1e-9, \
                "goodput exceeded offered load"
            goodput[(name, rate)] = gp
            rows.append(dict(
                config=f"{name}@{rate}rps",
                offered_rps=round(rep.offered_load_rps, 4),
                goodput_rps=round(gp, 4),
                deadline_hit_pct=round(
                    100.0 * rep.deadline_hit_rate(OPENLOOP_DEADLINE_MS), 2),
                p50_sojourn_ms=round(rep.p50_sojourn_ms, 2),
                p99_sojourn_ms=round(rep.p99_sojourn_ms, 2),
                peak_queue_depth=int(rep.queue_depth[1].max()),
            ))
    # the knee: past capacity, overlap + adaptive micro-batching sustains
    # strictly more deadline-meeting goodput than the blocking-send runtime
    top = OPENLOOP_RATES[-1]
    assert goodput[("overlap+amb4", top)] > goodput[("serial", top)], \
        "overlap+micro-batching must sustain higher goodput past the knee"
    return rows


#: closed-loop in-flight window for the scale section: must cover pipeline
#: depth × micro-batch (9 stages × 8) or batches starve and bubbles form
SCALE_CONCURRENCY = 128

#: offered load of the 50-node open-loop scale row: just under the event
#: path's steady-state completion rate (~7.6 rps), so the stream drains
SCALE_OPENLOOP_RPS = 7.0


def scale_rows(num_requests: int = 100_000, nodes: int = SCALE_NODES,
               budget_s: Optional[float] = SCALE_WALL_BUDGET_S):
    """The 100k × 50-node stream through both engine paths; asserts the
    wall-time budget (``budget_s=None`` disables the assert — the perf
    gate uses its own tolerance band and must report, not crash, on slow
    machines) and reports simulated-requests-per-wall-second."""
    g = mobilenetv2_graph()
    rows = []
    for name, cfg, arrivals in (
            ("fast-path-legacy-semantics", None, None),
            ("event-path-overlap+mb8",
             EngineConfig(transfer="overlap", micro_batch=8), None),
            ("openloop-poisson-overlap+mb8",
             EngineConfig(transfer="overlap", micro_batch=8),
             PoissonArrivals(rate_rps=SCALE_OPENLOOP_RPS,
                             seed=OPENLOOP_SEED))):
        cluster = make_synthetic_cluster(nodes, seed=7)
        d = DistributedInference(cluster, ModelPartitioner(g),
                                 method="planner")
        t0 = time.perf_counter()
        rep = d.run(num_requests, name=name, concurrency=SCALE_CONCURRENCY,
                    engine=cfg, arrivals=arrivals)
        wall_s = time.perf_counter() - t0
        if budget_s is not None and wall_s >= budget_s:
            raise RuntimeError(
                f"{name}: {num_requests} requests took {wall_s:.1f}s "
                f"(> {budget_s:.0f}s budget)")
        rows.append(dict(
            config=name,
            num_requests=num_requests,
            nodes=nodes,
            stages=len(d.plan.partitions),
            wall_s=round(wall_s, 2),
            sim_req_per_wall_s=round(num_requests / wall_s, 0),
            tail_throughput_rps=round(rep.tail_throughput_rps(), 4),
            sim_makespan_s=round(
                float(rep.columns.finish_ms.max()
                      - rep.columns.submit_ms.min()) / 1e3, 1),
        ))
    return rows


# --- batch-aware planning ----------------------------------------------------

#: the operating micro-batch of the batchcurve scenario (and the expected_k
#: the batch-aware planner costs stages at)
BC_K = 8
BC_REQUESTS = 800
BC_RATE_RPS = 75.0           # above the k=1 plan's batched capacity knee
BC_DEADLINE_MS = 800.0
BC_SEED = 17
#: large per-layer activation of the front half: at k=1 it fits the fast
#: node's memory; at BC_K the k-scaled working set crosses it (thrash knee)
BC_HEAVY_ACT = 8 * 1024 * 1024


def batchcurve_graph():
    """Synthetic 12-layer graph whose k=1-optimal and batch-aware-optimal
    plans differ: a compute-heavy, large-activation front half and a
    lighter, small-activation back half."""
    from repro.models.graph import LayerSpec, ModelGraph
    layers = []
    for i in range(6):
        ob = BC_HEAVY_ACT if i < 5 else 64 * 1024
        layers.append(LayerSpec(f"heavy{i}", "Conv2d", 0, 100_000,
                                out_bytes=ob))
    for i in range(6):
        layers.append(LayerSpec(f"light{i}", "Linear", 0, 60_000,
                                out_bytes=64 * 1024))
    return ModelGraph("batchcurve-toy", layers)


def batchcurve_cluster():
    """Three nodes where per-item speed and batched capacity disagree:
    one fast node with memory for the heavy stage at k=1 but not at
    ``BC_K``, and two slower nodes with headroom."""
    from repro.core.cluster import EdgeCluster
    from repro.core.cost_model import NodeProfile
    c = EdgeCluster()
    c.add_node("turbo-lowmem", NodeProfile(cpu=1.0, mem_mb=24.0,
                                           net_bw_mbps=8000.0))
    c.add_node("std-0", NodeProfile(cpu=0.55, mem_mb=1024.0,
                                    net_bw_mbps=8000.0))
    c.add_node("std-1", NodeProfile(cpu=0.55, mem_mb=1024.0,
                                    net_bw_mbps=8000.0))
    return c


def batchcurve_rows(num_requests: int = BC_REQUESTS):
    """k=1 planner vs batch-aware planner on the identical micro-batched
    open-loop stream, plus the calibrated-artifact comparison row. Fully
    deterministic (analytic cost model + seeded arrivals + committed
    artifact), so every field is guarded exactly."""
    from repro.core.cost_model import CALIBRATION_ARTIFACT, BatchCostModel
    from repro.core.planner import bottleneck_ms

    g = batchcurve_graph()
    rows = []
    result = {}
    for label, ek in (("planner-k1", 1), ("planner-batchaware", BC_K)):
        cluster = batchcurve_cluster()
        d = DistributedInference(cluster, ModelPartitioner(g),
                                 method="planner", expected_k=ek)
        cuts = [p.lo for p in d.plan.partitions] + [len(g.layers)]
        pred_k1 = bottleneck_ms(g, d.plan.partitions, d.placement, cluster)
        pred_kb = bottleneck_ms(g, d.plan.partitions, d.placement, cluster,
                                expected_k=BC_K)
        rep = d.run(num_requests, name=label,
                    engine=EngineConfig(transfer="overlap",
                                        micro_batch=BC_K,
                                        adaptive_batch=True),
                    arrivals=PoissonArrivals(rate_rps=BC_RATE_RPS,
                                             seed=BC_SEED))
        gp = rep.goodput_rps(BC_DEADLINE_MS)
        result[label] = dict(cuts=cuts, placement=dict(d.placement),
                             pred_kb=pred_kb, goodput=gp)
        rows.append(dict(
            config=label,
            expected_k=ek,
            cuts=cuts,
            assignment=[d.placement[i] for i in range(len(cuts) - 1)],
            predicted_bottleneck_k1_ms=round(pred_k1, 3),
            predicted_bottleneck_k8_ms=round(pred_kb, 3),
            goodput_rps=round(gp, 4),
            p50_sojourn_ms=round(rep.p50_sojourn_ms, 2),
            p99_sojourn_ms=round(rep.p99_sojourn_ms, 2),
            peak_queue_depth=int(rep.queue_depth[1].max()),
        ))
    a, b = result["planner-k1"], result["planner-batchaware"]
    assert a["cuts"] != b["cuts"] or a["placement"] != b["placement"], \
        "the batch-aware planner must pick a different plan at k=8"
    assert b["pred_kb"] < a["pred_kb"], (
        "batch-aware plan must have the lower predicted bottleneck at the "
        f"operating micro-batch: {b['pred_kb']:.2f} vs {a['pred_kb']:.2f}")
    assert b["goodput"] > a["goodput"], (
        "batch-aware plan must win on simulated open-loop goodput: "
        f"{b['goodput']:.2f} vs {a['goodput']:.2f}")

    # the committed kernel-calibration artifact vs the analytic fallback on
    # the paper testbed (deterministic: reads only the in-repo JSON)
    artifact = OUT_PATH.parent / CALIBRATION_ARTIFACT
    model = BatchCostModel.from_artifact(artifact)
    gm = mobilenetv2_graph()
    cluster = make_paper_cluster()
    d = DistributedInference(cluster, ModelPartitioner(gm), method="planner")
    kw = dict(batch=1, calibration=d.partitioner.calibration,
              speedup=d.deployer.speedup)
    rows.append(dict(
        config="calibrated-artifact-testbed",
        source=model.source,
        analytic_bottleneck_k4_ms=round(bottleneck_ms(
            gm, d.plan.partitions, d.placement, cluster,
            expected_k=4, **kw), 3),
        calibrated_bottleneck_k4_ms=round(bottleneck_ms(
            gm, d.plan.partitions, d.placement, cluster,
            expected_k=4, batch_model=model, **kw), 3),
    ))
    return rows


# --- fault storm --------------------------------------------------------------

#: the fault-storm scenario: one MobileNetV2 tenant on an 8-node fleet,
#: Poisson open-loop traffic with an SLO deadline, under transient node
#: crash/restart + transfer loss + execution faults + heavy-tailed
#: stragglers (one seeded draw sequence per policy, so every row is
#: bit-reproducible and guarded exactly by ``scripts/check_perf.py``)
FS_NODES = 8
FS_CRASH_NODES = 4           # only the first half of the fleet crash-cycles
FS_REQUESTS = 600
FS_RATE_RPS = 2.0
FS_DEADLINE_MS = 2500.0
FS_SEED = 23
FS_HAZARDS = dict(seed=FS_SEED, crash_mtbf_ms=30_000.0,
                  crash_mttr_ms=1500.0, loss_rate=0.01,
                  exec_fail_rate=0.01, straggler_rate=0.05,
                  straggler_shape=2.5, straggler_scale=2.0)
#: the retry policy shared by the non-naive rungs: backoff_base covers a
#: full crash_mttr window within two attempts, timeouts cut stragglers
#: loose at 3x the predicted stage time
FS_RETRY = dict(max_attempts=6, backoff_base_ms=250.0, timeout_slack=3.0)


def faultstorm_rows(num_requests: int = FS_REQUESTS):
    """Recovery-policy ladder under the identical seeded fault storm:
    naive fail-on-first-error, retry+timeout+backoff, and the full
    policy (retries + hedged duplicates + deadline-aware shedding). The
    full policy must beat naive on deadline-meeting goodput AND on p99
    sojourn over completed requests (asserted here, so the committed
    numbers are load-bearing)."""
    from repro.core.faults import FaultConfig
    from repro.core.tenancy import TenantRegistry, TenantTraffic

    # naive-fail is the ISSUE's fail-and-replan baseline: one attempt per
    # request, and every transient crash tears down and re-places the
    # partition plan (repair_on_crash=True); the resilient rungs instead
    # ride out a crash_mttr window with retry+backoff
    policies = [
        ("naive-fail", dict(max_attempts=1, repair_on_crash=True)),
        ("retry-backoff", dict(FS_RETRY)),
        ("resilient-hedge+shed", dict(FS_RETRY, hedge=True, shed=True)),
    ]
    g = mobilenetv2_graph()
    rows = []
    stats = {}
    for label, policy in policies:
        cluster = make_synthetic_cluster(FS_NODES, seed=3)
        fc = FaultConfig(crash_nodes=tuple(list(cluster.nodes)[:FS_CRASH_NODES]),
                         **FS_HAZARDS, **policy)
        reg = TenantRegistry(cluster)
        reg.add("storm", ModelPartitioner(g),
                traffic=TenantTraffic(
                    num_requests=num_requests, seed=FS_SEED,
                    concurrency=32, deadline_ms=FS_DEADLINE_MS,
                    arrivals=PoissonArrivals(rate_rps=FS_RATE_RPS,
                                             seed=FS_SEED)),
                method="planner")
        rep = reg.run(name=label,
                      engine=EngineConfig(transfer="overlap", micro_batch=4,
                                          adaptive_batch=True,
                                          faults=fc))["storm"]
        fs = rep.fault_stats
        done = rep.columns.status == 0
        p99_done = float(np.percentile(rep.columns.sojourn_ms[done], 99))
        gp = rep.goodput_rps(FS_DEADLINE_MS)
        stats[label] = (gp, p99_done)
        rows.append(dict(
            config=label,
            num_requests=num_requests,
            done=fs["done"], shed=fs["shed"], failed=fs["failed"],
            availability=round(fs["availability"], 4),
            retries=fs["retries_total"], hedges=fs["hedges_total"],
            crashes=fs["crashes"], restarts=fs["restarts"],
            goodput_rps=round(gp, 4),
            p99_done_sojourn_ms=round(p99_done, 2),
        ))
    naive, full = stats["naive-fail"], stats["resilient-hedge+shed"]
    assert full[0] > naive[0], (
        "the full recovery policy must beat naive fail-on-error on "
        f"deadline-meeting goodput: {full[0]:.3f} vs {naive[0]:.3f}")
    assert full[1] < naive[1], (
        "the full recovery policy must beat naive fail-on-error on p99 "
        f"sojourn over completed requests: {full[1]:.1f} vs {naive[1]:.1f}")
    return rows


# --- operator-DAG dataflow ----------------------------------------------------

#: the dagsweep scenario: an MoE-style branched plan (trunk -> 2 asymmetric
#: expert arms -> join -> tail) whose trunk head early-exits half the
#: requests, and a two-model cascade where the cheap branched model
#: escalates its misses into a MobileNetV2 tenant
DAG_REQUESTS = 400
DAG_SEED = 29
DAG_DEADLINE_MS = 2000.0
DAG_EXIT_PROB = 0.5
CASCADE_REQUESTS = 300
CASCADE_DEADLINE_MS = 2000.0


def dagsweep_rows(num_requests: int = DAG_REQUESTS):
    """Branched early-exit plans through the DAG planner + engine
    (per-exit-head goodput reported per row), then the model cascade vs
    serving every request on the expensive model alone — the cascade
    must win on end-to-end goodput (asserted here, so the committed
    numbers are load-bearing). Fully deterministic: closed-loop streams
    and seeded per-request exit draws."""
    from repro.core.tenancy import TenantRegistry, TenantTraffic
    from repro.models.graph import branched_graph

    rows = []
    g = branched_graph(exit_prob=DAG_EXIT_PROB)
    for label, cfg in (
            ("dag-branched-exit", None),
            ("dag-branched-exit-overlap+mb4",
             EngineConfig(transfer="overlap", micro_batch=4))):
        d = DistributedInference(make_paper_cluster(), ModelPartitioner(g),
                                 method="planner")
        rep = d.run(num_requests, name=label, seed=DAG_SEED, concurrency=8,
                    engine=cfg)
        row = rep.row()
        row["goodput_by_exit"] = {
            ("tail" if h < 0 else str(h)): round(v, 4)
            for h, v in sorted(rep.goodput_by_exit(DAG_DEADLINE_MS).items())}
        rows.append(row)

    # cascade vs expensive-only: identical request count; the cascade's
    # end-to-end latency of an escalated request spans cheap submit ->
    # big finish (escalations enter the big tenant in cheap-finish order,
    # so the positional match below is exact)
    gm = mobilenetv2_graph()
    reg = TenantRegistry(make_paper_cluster())
    reg.add("cheap", ModelPartitioner(branched_graph(exit_prob=DAG_EXIT_PROB)),
            traffic=TenantTraffic(num_requests=CASCADE_REQUESTS, seed=DAG_SEED,
                                  concurrency=8, escalate_to="big"),
            num_partitions=3, method="planner")
    reg.add("big", ModelPartitioner(gm),
            traffic=TenantTraffic(num_requests=CASCADE_REQUESTS, seed=DAG_SEED,
                                  concurrency=8),
            num_partitions=3, method="planner")
    res = reg.run(name="cascade")
    cheap, big = res.reports["cheap"], res.reports["big"]
    miss = cheap.columns.exit_head == -1
    order = np.argsort(cheap.columns.finish_ms[miss], kind="stable")
    start = np.concatenate([cheap.columns.submit_ms[~miss],
                            cheap.columns.submit_ms[miss][order]])
    finish = np.concatenate([cheap.columns.finish_ms[~miss],
                             big.columns.finish_ms])
    span_s = (float(finish.max()) - float(start.min())) / 1e3
    met = int(((finish - start) <= CASCADE_DEADLINE_MS).sum())
    cascade_goodput = met / span_s
    rows.append(dict(
        config="cascade-cheap->big",
        num_requests=CASCADE_REQUESTS,
        escalated=int(miss.sum()),
        exit_rate=round(float((~miss).mean()), 4),
        goodput_rps=round(cascade_goodput, 4),
        p99_end_to_end_ms=round(float(np.percentile(finish - start, 99)), 2),
    ))

    d = DistributedInference(make_paper_cluster(), ModelPartitioner(gm),
                             method="planner")
    rep = d.run(CASCADE_REQUESTS, name="big-only-baseline", seed=DAG_SEED,
                concurrency=8)
    baseline_goodput = rep.goodput_rps(CASCADE_DEADLINE_MS)
    row = rep.row()
    row["goodput_rps"] = round(baseline_goodput, 4)
    rows.append(row)
    assert cascade_goodput > baseline_goodput, (
        "the cascade must beat serving everything on the expensive model: "
        f"{cascade_goodput:.3f} vs {baseline_goodput:.3f} rps")
    return rows


# --- multi-tenant serving -----------------------------------------------------

#: the tenancy scale row: 3 tenants × 20 nodes × 10k open-loop requests
#: each, one shared event heap (the ISSUE-5 acceptance configuration)
MT_TENANTS = ("vision-a", "vision-b", "vision-c")
MT_NODES = 20
MT_REQUESTS = 10_000
MT_RATE_RPS = 0.8            # per tenant: aggregate just under capacity
MT_DEADLINE_MS = 3000.0
MT_WALL_BUDGET_S = 10.0

#: arbitration comparison: a tight fleet over a slow fabric, where a
#: shared-node throttle makes every controller want to move at once and
#: full-replan transfers are expensive enough to fail the economics gate
ARB_NODES = 10
ARB_CLUSTER_SEED = 5
ARB_REQUESTS = 2_000
ARB_RATE_RPS = 0.6
ARB_NET_BW_MBPS = 30.0
ARB_DEADLINE_MS = 1500.0
ARB_THROTTLE_AT_MS = 30_000.0
ARB_PARTIAL_K = 2


def _mt_registry(nodes: int, cluster_seed: int, num_requests: int,
                 rate_rps: float, deadline_ms: float,
                 adaptive: bool = False, partial_k: int = 0,
                 net_bw_mbps: Optional[float] = None):
    """A fresh registry of three MobileNetV2 tenants with Poisson
    open-loop traffic on a synthetic cluster (jointly planner-deployed:
    each tenant plans around the budgets earlier tenants committed)."""
    from repro.core.adaptation import AdaptationConfig
    from repro.core.tenancy import TenantRegistry, TenantTraffic

    cluster = make_synthetic_cluster(nodes, seed=cluster_seed)
    if net_bw_mbps is not None:
        for nid in cluster.nodes:
            cluster.set_profile(nid, net_bw_mbps=net_bw_mbps)
    reg = TenantRegistry(cluster)
    g = mobilenetv2_graph()
    for i, name in enumerate(MT_TENANTS):
        kw = dict(method="planner")
        if adaptive:
            kw.update(adaptation=AdaptationConfig(
                partial_migration_k=partial_k))
        reg.add(name, ModelPartitioner(g),
                traffic=TenantTraffic(
                    num_requests=num_requests,
                    arrivals=PoissonArrivals(rate_rps=rate_rps, seed=i),
                    concurrency=32, seed=i, deadline_ms=deadline_ms),
                **kw)
    return reg


def _shared_throttle(reg):
    """Throttle the node serving the most tenants to the paper's
    low-resource floor — the drift that makes every tenant's controller
    want to migrate at the same control tick."""
    from repro.core.adaptation import cpu_throttle
    shared = {}
    for t in reg.tenants.values():
        for nid in set(t.placement.values()):
            shared[nid] = shared.get(nid, 0) + 1
    victim = max(sorted(shared), key=lambda nid: shared[nid])
    return [cpu_throttle(ARB_THROTTLE_AT_MS, victim, cpu=0.1, mem_mb=256.0)]


def multitenant_rows(num_requests: int = MT_REQUESTS,
                     budget_s: Optional[float] = MT_WALL_BUDGET_S):
    """The tenancy sections: the 3×20×10k shared-heap scale row, then the
    arbitration-vs-independent comparison under a shared-node throttle."""
    rows = []

    # (a) scale: one shared event heap interleaving 3 tenants' streams
    reg = _mt_registry(MT_NODES, 7, num_requests, MT_RATE_RPS,
                       MT_DEADLINE_MS)
    t0 = time.perf_counter()
    rep = reg.run(name="mt-3x20-openloop",
                  engine=EngineConfig(transfer="overlap", micro_batch=4))
    wall_s = time.perf_counter() - t0
    if budget_s is not None and wall_s >= budget_s:
        raise RuntimeError(
            f"multitenant scale: {rep.num_requests} requests took "
            f"{wall_s:.1f}s (> {budget_s:.0f}s budget)")
    row = rep.row()
    row.update(nodes=MT_NODES, wall_s=round(wall_s, 2),
               sim_req_per_wall_s=round(rep.num_requests / wall_s, 0))
    rows.append(row)

    # (b) arbitration: cross-tenant best-net-gain + partial migrations
    # vs per-tenant independent full re-planning, identical drift
    goodput = {}
    for label, arbitration, partial_k in (
            ("mt-arbitrated+partial", True, ARB_PARTIAL_K),
            ("mt-independent-replan", False, 0)):
        reg = _mt_registry(ARB_NODES, ARB_CLUSTER_SEED, ARB_REQUESTS,
                           ARB_RATE_RPS, ARB_DEADLINE_MS, adaptive=True,
                           partial_k=partial_k,
                           net_bw_mbps=ARB_NET_BW_MBPS)
        rep = reg.run(name=label, scenario=_shared_throttle(reg),
                      engine=EngineConfig(transfer="overlap",
                                          micro_batch=4),
                      arbitration=arbitration)
        goodput[label] = rep.goodput_rps()
        row = rep.row()
        row.update(nodes=ARB_NODES)
        rows.append(row)
    assert (goodput["mt-arbitrated+partial"]
            > goodput["mt-independent-replan"]), (
        "cross-tenant arbitration with partial migrations must beat "
        f"independent re-planning on aggregate goodput: {goodput}")
    return rows


def run(scale_requests: int = 100_000, write: bool = True,
        budget_s: Optional[float] = SCALE_WALL_BUDGET_S) -> dict:
    """Run all sections; optionally write ``BENCH_pipeline.json``.

    ``scale_requests`` shrinks the scale section for the perf-regression
    check's reduced configuration (``scripts/check_perf.py``); the
    multitenant section always runs at full size (its simulated metrics
    are compared exactly against the committed baseline). ``budget_s``
    None disables every wall-time assert (the gate bands wall time
    itself).
    """
    result = dict(
        table1=table1_rows(),
        modes=mode_rows(),
        openloop=openloop_rows(),
        batchcurve=batchcurve_rows(),
        faultstorm=faultstorm_rows(),
        dagsweep=dagsweep_rows(),
        scale=scale_rows(scale_requests, budget_s=budget_s),
        multitenant=multitenant_rows(
            budget_s=MT_WALL_BUDGET_S if budget_s is not None else None),
    )
    if write:
        OUT_PATH.write_text(json.dumps(result, indent=2) + "\n")
    return result


if __name__ == "__main__":
    out = run()
    for section, rows in out.items():
        print(f"\n# {section}")
        for row in rows:
            cfg = row.pop("config", "")
            print(",".join([f"pipeline/{cfg}"]
                           + [f"{k}={v}" for k, v in row.items()]))
    print(f"\nwrote {OUT_PATH}")
