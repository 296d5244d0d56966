"""Fault-injection lifecycle suite: conservation, recovery, and golden
fingerprints.

Three layers of coverage for ``core.faults``:

* **Unit** — ``FaultConfig`` validation, the ``EngineConfig`` fabric
  guard, and the node-recovery regression (a crashed node must drop out
  of the scheduler's candidate set and return to it — and win dispatches
  again — after recovery).
* **Regression** — a planned ``ScenarioEvent`` node death that strands
  queued work used to raise ``RuntimeError("... lost in flight")``; it
  must now complete with the stranded requests accounted as ``failed``
  (reason ``node-lost``), and the run is held to a golden fingerprint.
* **Generative sweep** — a seeded sampler (same ``random.Random``
  pattern as ``tests/test_engine_parity.py``) draws 120 faulted
  configurations spanning crash/restart x transfer loss x execution
  faults x stragglers x timeout/retry/hedge/shed policy x {serial,
  legacy, overlap} transfer x arrival processes x 1-3 tenants x optional
  cache/adaptation. Every configuration must reproduce its entry of
  ``FAULT_GOLDEN`` (``conftest.fingerprint``: event count, per-tenant
  done/shed/failed counts, finish-time sum, p99 sojourn, network bytes,
  batch histogram, adaptation-log length, arbitration summary; recorded
  where two independent event cores, this heap loop and a since-removed
  time-wheel core, agreed bit for bit) while satisfying conservation:
  every request terminates in exactly one of {done, shed,
  failed-with-reason}. An all-hazards-off ``FaultConfig`` must be
  bit-identical to ``faults=None``.

A failing sweep config prints its sampler seed and index; replay with
``_config_at(SAMPLER_SEED, index)``. Tier-1 runs a fixed prefix of the
sequence, the bulk is ``slow``-marked.
"""

import random

import numpy as np
import pytest
from conftest import fingerprint

from repro.core.adaptation import node_death, node_recovery
from repro.core.cluster import make_paper_cluster, make_synthetic_cluster
from repro.core.engine import EngineConfig
from repro.core import engine as eng_mod
from repro.core.faults import (FaultConfig, STATUS_DONE, STATUS_FAILED,
                               STATUS_SHED)
from repro.core.monitor import ResourceMonitor
from repro.core.partitioner import ModelPartitioner
from repro.core.scheduler import TaskScheduler
from repro.core.tenancy import TenantRegistry, TenantTraffic
from repro.core.traffic import DeterministicArrivals, PoissonArrivals
from repro.models.graph import mobilenetv2_graph

GRAPH = mobilenetv2_graph()

#: the generative space's seed — part of every failure's reproduction
#: string, never change without regenerating expectations
SAMPLER_SEED = 20260810

NUM_CONFIGS = 120
TIER1_CONFIGS = 6
CHUNK = 38   # slow-sweep chunk size (3 chunks over the remaining 114)


# --- sampler -----------------------------------------------------------------


def _sample_config(rnd: random.Random) -> dict:
    """One faulted engine configuration; pure function of the passed
    ``Random`` so config i replays from (SAMPLER_SEED, i)."""
    n_tenants = rnd.choice((1, 1, 1, 2, 3))
    adaptive = rnd.random() < 0.2
    crash = rnd.random() < 0.45
    cfg = dict(
        transfer=rnd.choice(("legacy", "serial", "overlap")),
        micro_batch=rnd.choice((1, 2, 4)),
        adaptive_batch=rnd.random() < 0.5,
        arrivals_kind=rnd.choice(("closed", "det", "poisson")),
        arrival_rate=round(rnd.uniform(2.0, 14.0), 2),
        arrival_seed=rnd.randrange(1 << 16),
        n_tenants=n_tenants,
        n_nodes=rnd.choice((4, 5, 6)),
        cluster_seed=rnd.randrange(1 << 16),
        n_requests=rnd.choice((30, 45, 60)),
        concurrency=rnd.choice((2, 4)),
        repeat_rate=rnd.choice((0.0, 0.3)),
        use_cache=rnd.random() < 0.25,
        adaptive=adaptive,
        arbitration=adaptive and n_tenants > 1 and rnd.random() < 0.5,
        deadline_ms=rnd.choice((1200.0, 2000.0, 4000.0)),
        scenario_kind=rnd.choice(("none", "none", "none", "none",
                                  "death-recovery")),
        scenario_at=round(rnd.uniform(400.0, 2500.0), 1),
        stream_seed=rnd.randrange(1 << 16),
        # --- hazards: independent coin flips so single-kind and
        # combined-kind storms both appear in the space ---
        fault_seed=rnd.randrange(1 << 16),
        crash_mtbf_ms=round(rnd.uniform(1500.0, 8000.0), 1) if crash else 0.0,
        crash_mttr_ms=round(rnd.uniform(300.0, 1500.0), 1),
        crash_subset=crash and rnd.random() < 0.5,
        loss_rate=(round(rnd.uniform(0.005, 0.05), 4)
                   if rnd.random() < 0.4 else 0.0),
        exec_fail_rate=(round(rnd.uniform(0.005, 0.05), 4)
                        if rnd.random() < 0.4 else 0.0),
        straggler_rate=(round(rnd.uniform(0.02, 0.12), 4)
                        if rnd.random() < 0.4 else 0.0),
        timeout_slack=(round(rnd.uniform(2.5, 6.0), 2)
                       if rnd.random() < 0.5 else 0.0),
        hedge=rnd.random() < 0.5,
        shed=rnd.random() < 0.4,
        max_attempts=rnd.choice((2, 3, 4, 6)),
        retry_budget=rnd.choice((None, None, 8, 30)),
    )
    return cfg


def _config_at(seed: int, index: int) -> dict:
    """Replay the sampler: the config at ``index`` of the seeded
    sequence — the reproduction recipe printed on failure."""
    rnd = random.Random(seed)
    for _ in range(index):
        _sample_config(rnd)
    return _sample_config(rnd)


def _fault_config(cfg: dict, cluster) -> FaultConfig:
    nids = tuple(cluster.nodes)
    targets = ()
    if cfg["crash_subset"]:
        targets = nids[:max(1, len(nids) // 2)]
    return FaultConfig(
        seed=cfg["fault_seed"],
        crash_mtbf_ms=cfg["crash_mtbf_ms"],
        crash_mttr_ms=cfg["crash_mttr_ms"],
        crash_nodes=targets,
        loss_rate=cfg["loss_rate"],
        exec_fail_rate=cfg["exec_fail_rate"],
        straggler_rate=cfg["straggler_rate"],
        timeout_slack=cfg["timeout_slack"],
        hedge=cfg["hedge"],
        shed=cfg["shed"],
        max_attempts=cfg["max_attempts"],
    )


def _make_arrivals(cfg: dict, tenant_idx: int):
    kind = cfg["arrivals_kind"]
    if kind == "closed":
        return None
    if kind == "det":
        return DeterministicArrivals.at_rate(cfg["arrival_rate"])
    return PoissonArrivals(rate_rps=cfg["arrival_rate"],
                           seed=cfg["arrival_seed"] + tenant_idx)


def _scenario(cfg: dict, cluster):
    if cfg["scenario_kind"] == "none":
        return None
    at = cfg["scenario_at"]
    nid = list(cluster.nodes)[cfg["cluster_seed"] % len(cluster.nodes)]
    return [node_death(at, nid), node_recovery(at + 1200.0, nid)]


def _run(cfg: dict, faults="sample", **engine_kw):
    """Build a fresh cluster + registry from the config and run it;
    returns (reports dict, event count). ``engine_kw`` overrides the
    config's ``EngineConfig`` fields."""
    cluster = make_synthetic_cluster(cfg["n_nodes"],
                                     seed=cfg["cluster_seed"] % 1000)
    if faults == "sample":
        faults = _fault_config(cfg, cluster)
    reg = TenantRegistry(cluster)
    eng_mod.LAST_EVENT_COUNT = None
    for i in range(cfg["n_tenants"]):
        reg.add(f"t{i}", ModelPartitioner(GRAPH),
                traffic=TenantTraffic(
                    num_requests=cfg["n_requests"],
                    repeat_rate=cfg["repeat_rate"],
                    seed=cfg["stream_seed"] + i,
                    concurrency=cfg["concurrency"],
                    deadline_ms=cfg["deadline_ms"],
                    retry_budget=cfg["retry_budget"],
                    arrivals=_make_arrivals(cfg, i)),
                num_partitions=3, method="planner",
                use_cache=cfg["use_cache"],
                adaptive=cfg["adaptive"])
    kw = dict(transfer=cfg["transfer"], micro_batch=cfg["micro_batch"],
              adaptive_batch=cfg["adaptive_batch"], faults=faults)
    kw.update(engine_kw)
    result = reg.run(scenario=_scenario(cfg, cluster),
                     engine=EngineConfig(**kw),
                     arbitration=cfg["arbitration"])
    return result, eng_mod.LAST_EVENT_COUNT


# --- invariants --------------------------------------------------------------


def _assert_conservation(rep, repro: str):
    """Every request terminates in exactly one of {done, shed, failed},
    the counts partition the stream, and the published ``fault_stats``
    agree with the columns."""
    cols = rep.columns
    status = cols.status
    n = len(cols)
    assert np.all((status >= STATUS_DONE) & (status <= STATUS_FAILED)), repro
    n_done = int(np.count_nonzero(status == STATUS_DONE))
    n_shed = int(np.count_nonzero(status == STATUS_SHED))
    n_failed = int(np.count_nonzero(status == STATUS_FAILED))
    assert n_done + n_shed + n_failed == n, repro
    fs = rep.fault_stats
    assert fs is not None, repro
    assert fs["done"] == n_done == rep.done_count, repro
    assert fs["shed"] == n_shed == rep.shed_count, repro
    assert fs["failed"] == n_failed == rep.failed_count, repro
    assert sum(fs["failed_reasons"].values()) == n_failed, repro
    assert fs["availability"] == rep.availability, repro
    assert fs["retries_total"] == int(cols.retries.sum()), repro
    assert fs["hedges_total"] == int(cols.hedges.sum()), repro
    # timeline sanity: every request got a terminal timestamp no earlier
    # than its submit; done requests always pass the scheduling overhead
    # so their finish is strictly positive (a request shed at t=0 — the
    # closed loop's first submit instant — legitimately finishes at 0.0)
    assert np.all(cols.finish_ms[status == STATUS_DONE] > 0.0), repro
    assert np.all(cols.finish_ms >= cols.submit_ms), repro
    assert np.all(cols.submit_ms >= cols.arrival_ms), repro


def _assert_golden(index: int):
    cfg = _config_at(SAMPLER_SEED, index)
    repro = (f"config {index} of sampler seed {SAMPLER_SEED} — replay "
             f"with tests.test_faults._config_at({SAMPLER_SEED}, "
             f"{index}) = {cfg!r}")
    res, nev = _run(cfg)
    got = fingerprint(res, nev)
    assert got == FAULT_GOLDEN[index], (
        f"fingerprint {got!r} != golden {FAULT_GOLDEN[index]!r}\n{repro}")
    for rep in res.reports.values():
        _assert_conservation(rep, repro)


@pytest.mark.parametrize("index", range(TIER1_CONFIGS))
def test_fault_parity_tier1(index):
    """The first TIER1_CONFIGS sampled storms reproduce their golden
    fingerprints and conserve requests — the always-on drift gate."""
    _assert_golden(index)


@pytest.mark.slow
@pytest.mark.parametrize("lo", range(TIER1_CONFIGS, NUM_CONFIGS, CHUNK))
def test_fault_parity_sweep(lo):
    """The remaining sampled fault storms, in chunks (deselect with
    ``-m 'not slow'``)."""
    for index in range(lo, min(lo + CHUNK, NUM_CONFIGS)):
        _assert_golden(index)


def test_sampler_is_deterministic():
    assert _config_at(SAMPLER_SEED, 9) == _config_at(SAMPLER_SEED, 9)
    assert _config_at(SAMPLER_SEED, 9) != _config_at(SAMPLER_SEED, 10)


# --- zero-hazard identity ----------------------------------------------------


@pytest.mark.parametrize("index", (0, 3, 7))
@pytest.mark.parametrize("transfer", ("serial", "overlap"))
def test_all_zero_faultconfig_is_identity(index, transfer):
    """A ``FaultConfig`` with every hazard disabled performs zero RNG
    draws and must be bit-identical to ``faults=None``, under blocking
    and overlapped transfers (scenario-free configs: a scenario death
    takes the fault-mode crash path, which legitimately differs from
    planned-replanning)."""
    cfg = dict(_config_at(SAMPLER_SEED, index),
               scenario_kind="none", shed=False)
    zero = FaultConfig(seed=cfg["fault_seed"])
    rz, _ = _run(cfg, faults=zero, transfer=transfer)
    rn, _ = _run(cfg, faults=None, transfer=transfer)
    for name, z in rz.reports.items():
        n = rn.reports[name]
        assert z.columns.bitwise_equal(n.columns), name
        assert z.batch_hist == n.batch_hist, name
        assert z.network_bytes == n.network_bytes, name
        # the fault layer was armed, so stats are published — but empty
        assert z.fault_stats["failed"] == 0 and z.fault_stats["shed"] == 0
        assert z.fault_stats["availability"] == 1.0
        assert n.fault_stats is None


# --- config validation -------------------------------------------------------


def test_faultconfig_validation():
    FaultConfig()                                     # all defaults legal
    with pytest.raises(ValueError):
        FaultConfig(crash_mtbf_ms=-1.0)
    with pytest.raises(ValueError):
        FaultConfig(crash_mttr_ms=0.0)
    with pytest.raises(ValueError):
        FaultConfig(loss_rate=1.5)
    with pytest.raises(ValueError):
        FaultConfig(timeout_slack=0.8)                # must be 0 or > 1
    with pytest.raises(ValueError):
        FaultConfig(max_attempts=0)
    with pytest.raises(ValueError):
        FaultConfig(backoff_mult=0.5)


def test_faults_require_isolated_fabric():
    with pytest.raises(AssertionError):
        EngineConfig(fabric="maxmin", faults=FaultConfig())
    EngineConfig(fabric="isolated", faults=FaultConfig())


# --- node recovery regression ------------------------------------------------


def test_node_recovery_restores_scheduler_eligibility():
    """A crashed node leaves the scheduler's candidate set; after
    recovery it is eligible again and — when the others are busy — wins
    the dispatch."""
    cluster = make_paper_cluster()
    monitor = ResourceMonitor(cluster)
    sched = TaskScheduler()
    victim = next(iter(cluster.nodes))

    snaps = monitor.poll(force=True)
    assert victim in {s.node_id for s in snaps.values() if s.online}

    cluster.remove_node(victim)
    snaps = monitor.poll(force=True)
    online = [s for s in snaps.values() if s.online]
    assert victim not in {s.node_id for s in online}
    assert sched.select_node(online) != victim
    assert sched.select_alternate(online, exclude=()) != victim

    cluster.restore_node(victim)
    snaps = monitor.poll(force=True)
    online = [s for s in snaps.values() if s.online]
    assert victim in {s.node_id for s in online}
    # make everyone else ineligible: the recovered node must win
    others = tuple(n for n in cluster.nodes if n != victim)
    assert sched.select_alternate(online, exclude=others) == victim


@pytest.mark.parametrize("micro_batch", (1, 2))
def test_node_recovery_dispatches_land_on_recovered_node(micro_batch):
    """Targeted crash/restart of one placement node: the run keeps
    going, the node recovers, and requests complete end-to-end after
    recovery — only possible if dispatches land on the recovered node
    again (the placement pins one stage to it). Holds with and without
    micro-batching."""
    cluster = make_paper_cluster()
    victim = list(cluster.nodes)[0]
    faults = FaultConfig(seed=5, crash_mtbf_ms=900.0, crash_mttr_ms=250.0,
                         crash_nodes=(victim,), max_attempts=8,
                         backoff_base_ms=40.0)
    reg = TenantRegistry(cluster)
    reg.add("t0", ModelPartitioner(GRAPH),
            traffic=TenantTraffic(num_requests=60, seed=3, concurrency=2,
                                  arrivals=DeterministicArrivals.at_rate(8.0)),
            num_partitions=3, method="planner")
    res = reg.run(engine=EngineConfig(transfer="overlap",
                                      micro_batch=micro_batch,
                                      faults=faults))
    rep = res["t0"]
    fs = rep.fault_stats
    assert fs["crashes"] >= 1 and fs["restarts"] >= 1, fs
    assert cluster.nodes[victim].online
    # the stream outlives several crash/restart cycles: most requests
    # complete, and completion requires the victim's pinned stage
    assert fs["done"] >= 45, fs
    _assert_conservation(rep, f"micro_batch={micro_batch}")


# --- scenario-death stranding regression -------------------------------------


def _death_cfg() -> dict:
    """A config whose planned node death strands queued work — the shape
    that used to raise ``RuntimeError('... lost in flight')``."""
    return dict(
        transfer="overlap", micro_batch=2, adaptive_batch=False,
        arrivals_kind="det", arrival_rate=40.0, arrival_seed=1,
        n_tenants=1, n_nodes=4, cluster_seed=2, n_requests=50,
        concurrency=8, repeat_rate=0.0, use_cache=False, adaptive=False,
        arbitration=False, deadline_ms=2000.0, scenario_kind="none",
        scenario_at=0.0, stream_seed=7, fault_seed=0,
        crash_mtbf_ms=0.0, crash_mttr_ms=1000.0, crash_subset=False,
        loss_rate=0.0, exec_fail_rate=0.0, straggler_rate=0.0,
        timeout_slack=0.0, hedge=False, shed=False, max_attempts=4,
        retry_budget=None)


def test_scenario_death_accounts_stranded_requests():
    """Satellite regression for the in-flight-loss crash: a scenario
    node death with no recovery, timed so requests are queued on the
    dead node, completes with the stranded requests marked failed
    (reason ``node-lost``) instead of raising, and reproduces its golden
    fingerprint."""
    cfg = _death_cfg()

    def run_death():
        cluster = make_synthetic_cluster(cfg["n_nodes"], seed=2)
        reg = TenantRegistry(cluster)
        reg.add("t0", ModelPartitioner(GRAPH),
                traffic=TenantTraffic(
                    num_requests=cfg["n_requests"], seed=cfg["stream_seed"],
                    concurrency=cfg["concurrency"],
                    arrivals=DeterministicArrivals.at_rate(
                        cfg["arrival_rate"])),
                num_partitions=3, method="planner")
        nid = list(cluster.nodes)[0]
        scenario = [node_death(300.0, nid)]
        eng_mod.LAST_EVENT_COUNT = None
        return reg.run(scenario=scenario,
                       engine=EngineConfig(transfer="overlap",
                                           micro_batch=2))

    res = run_death()
    assert fingerprint(res, eng_mod.LAST_EVENT_COUNT) == DEATH_GOLDEN
    h = res["t0"]
    # either the run drained cleanly (nothing was in flight at death) or
    # the stranded tail is accounted — never an exception either way
    if h.fault_stats is not None:
        assert h.fault_stats["failed"] > 0
        assert set(h.fault_stats["failed_reasons"]) == {"node-lost"}
        n_failed = int(np.count_nonzero(h.columns.status == STATUS_FAILED))
        assert n_failed == h.fault_stats["failed"]
        assert np.all(h.columns.finish_ms > 0.0)


# --- policy efficacy ---------------------------------------------------------


@pytest.mark.parametrize("micro_batch", (1, 2))
def test_retry_policy_beats_single_attempt(micro_batch):
    """Under a lossy/flaky storm, the recovery policy (retries + hedges)
    completes more requests than a naive single-attempt policy — the
    qualitative claim the faultstorm bench quantifies — with and without
    micro-batching."""
    base = dict(seed=11, crash_mtbf_ms=5000.0, crash_mttr_ms=600.0,
                loss_rate=0.03, exec_fail_rate=0.03, straggler_rate=0.05,
                timeout_slack=4.0)
    naive = FaultConfig(max_attempts=1, hedge=False, **base)
    resilient = FaultConfig(max_attempts=5, hedge=True, **base)

    def run(policy):
        cluster = make_synthetic_cluster(5, seed=9)
        reg = TenantRegistry(cluster)
        reg.add("t0", ModelPartitioner(GRAPH),
                traffic=TenantTraffic(num_requests=80, seed=21,
                                      concurrency=4,
                                      arrivals=PoissonArrivals(
                                          rate_rps=10.0, seed=13)),
                num_partitions=3, method="planner")
        return reg.run(engine=EngineConfig(transfer="overlap",
                                           micro_batch=micro_batch,
                                           faults=policy))["t0"]

    rn = run(naive)
    rr = run(resilient)
    _assert_conservation(rn, "naive")
    _assert_conservation(rr, "resilient")
    assert rr.fault_stats["done"] > rn.fault_stats["done"], (
        rn.fault_stats, rr.fault_stats)


# --- golden fingerprints (conftest.fingerprint) ------------------------------

#: the scenario-death run of ``test_scenario_death_accounts_stranded_requests``
DEATH_GOLDEN = (342, None, (
    ('t0', 50, 0, 0, 802798.605, 28683.1784, 13171200.0, None,
     ((1, 42), (2, 54))),))

FAULT_GOLDEN = {
    0: (225, None, (
        ('t0', 10, 7, 28, 140019.155, 5206.33924, 3763200.0, None,
         ((1, 32),)),)),
    1: (1041, None, (
        ('t0', 60, 0, 0, 920902.446, 9064.73105, 15805440.0, None,
         ((1, 184),)),
        ('t1', 60, 0, 0, 2436862.99, 46793.8053, 13171200.0, None,
         ((1, 184),)),)),
    2: (589, None, (
        ('t0', 3, 44, 13, 245548.993, 3718.46444, 2370816.0, None,
         ((1, 24),)),
        ('t1', 5, 55, 0, 269370.447, 4609.40193, 7037184.0, None,
         ((1, 28),)),
        ('t2', 3, 56, 1, 268195.052, 4494.22266, 2107392.0, None,
         ((1, 18),)),)),
    3: (141, None, (
        ('t0', 5, 24, 1, 60819.3413, 2817.75359, 2709504.0, None,
         ((1, 24),)),)),
    4: (507, None, (
        ('t0', 30, 0, 0, 349198.638, 14483.468, 11289600.0, None,
         ((1, 91), (2, 1))),
        ('t1', 30, 0, 0, 312276.775, 13610.1329, 9031680.0, None,
         ((1, 90),)),)),
    5: (859, None, (
        ('t0', 45, 0, 0, 1352439.72, 3259.67694, 16934400.0, None,
         ((1, 13), (2, 61))),
        ('t1', 45, 0, 0, 1366079.9, 3447.99641, 16934400.0, None,
         ((1, 90), (2, 23))),
        ('t2', 45, 0, 0, 1318152.03, 3944.76057, 11854080.0, None,
         ((1, 123), (2, 6))),)),
    6: (724, None, (
        ('t0', 45, 0, 0, 604658.835, 22541.6115, 11854080.0, None,
         ((1, 109), (2, 13))),
        ('t1', 45, 0, 0, 723853.75, 24775.5725, 11854080.0, None,
         ((1, 111), (2, 12))),)),
    7: (823, None, (
        ('t0', 23, 0, 7, 398067.759, 10141.4387, 8655360.0, None,
         ((1, 101),)),
        ('t1', 26, 0, 4, 480035.808, 11034.2903, 14488320.0, None,
         ((1, 114),)),)),
    8: (307, None, (
        ('t0', 27, 0, 3, 353779.374, 6801.38572, 12945408.0, 66,
         ((1, 76), (2, 23))),)),
    9: (397, None, (
        ('t0', 6, 38, 1, 149556.524, 2659.09128, 1843968.0, None,
         ((1, 20),)),
        ('t1', 8, 37, 0, 144153.473, 2668.70211, 1756160.0, None,
         ((1, 24),)),
        ('t2', 5, 40, 0, 126515.899, 2492.97221, 3198720.0, None,
         ((1, 15),)),)),
    10: (389, None, (
        ('t0', 45, 0, 0, 463726.787, 15259.9203, 16934400.0, None,
         ((1, 135),)),)),
    11: (222, None, (
        ('t0', 30, 0, 0, 176914.059, 911.754018, 11289600.0, None,
         ((1, 90),)),)),
    12: (147, None, (
        ('t0', 9, 21, 0, 126000.37, 4798.51392, 2370816.0, None,
         ((1, 29),)),)),
    13: (325, None, (
        ('t0', 27, 0, 18, 677579.433, 2679.72281, 9257472.0, None,
         ((1, 106),)),)),
    14: (436, None, (
        ('t0', 45, 0, 0, 388072.006, 8161.60876, 17950464.0, 22,
         ((1, 150),)),)),
    15: (271, None, (
        ('t0', 30, 0, 0, 435610.17, 23083.9981, 11289600.0, None,
         ((1, 92),)),)),
    16: (536, None, (
        ('t0', 59, 0, 1, 1130455.58, 31053.6732, 15617280.0, 2,
         ((1, 189),)),)),
    17: (350, None, (
        ('t0', 22, 0, 23, 632845.2, 9755.05383, 6660864.0, None,
         ((1, 80),)),)),
    18: (663, None, (
        ('t0', 45, 0, 0, 623344.591, 2619.05891, 22014720.0, 0,
         ((1, 135),)),
        ('t1', 45, 0, 0, 592823.805, 2284.19832, 16934400.0, 0,
         ((1, 135),)),)),
    19: (164, None, (
        ('t0', 8, 37, 0, 141982.514, 4457.17476, 3311616.0, 5, ((1, 29),)),)),
    20: (252, None, (
        ('t0', 9, 21, 0, 151503.53, 3676.41312, 2370816.0, None,
         ((1, 29),)),
        ('t1', 10, 20, 0, 147617.278, 3468.47819, 2195200.0, None,
         ((1, 31),)),)),
    21: (1160, None, (
        ('t0', 45, 0, 0, 580509.716, 4347.19007, 14488320.0, None,
         ((1, 120),)),
        ('t1', 45, 0, 0, 636419.833, 18321.4333, 12832512.0, None,
         ((1, 135),)),
        ('t2', 45, 0, 0, 617290.919, 3531.8296, 9746688.0, None,
         ((1, 112),)),)),
    22: (216, None, (
        ('t0', 11, 49, 0, 235219.917, 2115.27724, 2897664.0, None,
         ((1, 33),)),)),
    23: (607, None, (
        ('t0', 51, 0, 9, 856844.52, 22747.0189, 15466752.0, None,
         ((1, 117), (2, 35))),)),
    24: (137, None, (
        ('t0', 12, 18, 0, 60986.3776, 1673.07753, 2634240.0, None,
         ((1, 36),)),)),
    25: (648, None, (
        ('t0', 60, 0, 0, 834246.99, 14724.6135, 22579200.0, None,
         ((1, 186),)),)),
    26: (265, None, (
        ('t0', 30, 0, 0, 203375.247, 2632.13165, 9784320.0, 20,
         ((1, 51), (2, 22))),)),
    27: (284, None, (
        ('t0', 30, 0, 0, 148464.172, 1722.0616, 11289600.0, None,
         ((1, 92),)),)),
    28: (446, None, (
        ('t0', 30, 0, 0, 342436.687, 2658.12646, 11289600.0, None,
         ((1, 90),)),
        ('t1', 30, 0, 0, 383085.252, 4741.37196, 9031680.0, None,
         ((1, 90),)),)),
    29: (475, None, (
        ('t0', 45, 0, 0, 460264.056, 4363.09691, 16934400.0, None,
         ((1, 134), (2, 1))),)),
    30: (482, None, (
        ('t0', 45, 0, 0, 319375.091, 9891.58134, 16934400.0, 20,
         ((1, 137),)),)),
    31: (231, None, (
        ('t0', 3, 0, 57, 252983.921, 2781.50536, 1618176.0, None,
         ((1, 10), (2, 2), (3, 3))),)),
    32: (404, None, (
        ('t0', 45, 0, 0, 654607.784, 21834.75, 14337792.0, 32, ((1, 131),)),)),
    33: (270, None, (
        ('t0', 30, 0, 0, 205549.577, 4676.58673, 11665920.0, 11,
         ((1, 96),)),)),
    34: (201, None, (
        ('t0', 17, 28, 0, 262834.58, 5886.46992, 6397440.0, None,
         ((1, 51),)),)),
    35: (248, None, (
        ('t0', 30, 0, 0, 221996.717, 10106.0418, 3198720.0, None,
         ((1, 59), (2, 11), (3, 3))),)),
    36: (872, None, (
        ('t0', 45, 0, 0, 2159780.51, 13057.1702, 8404480.0, 76,
         ((1, 95), (2, 1))),
        ('t1', 45, 0, 0, 2361140.91, 13635.3617, 7727104.0, 76,
         ((1, 83), (2, 7))),
        ('t2', 45, 0, 0, 2131748.63, 12035.3577, 8843520.0, 76,
         ((1, 89), (2, 4))),)),
    37: (390, None, (
        ('t0', 16, 0, 29, 296343.609, 1620.04497, 13171200.0, None,
         ((1, 94),)),)),
    38: (252, None, (
        ('t0', 3, 0, 42, 456277.329, 10289.287, 2069760.0, None,
         ((1, 25),)),)),
    39: (227, None, (
        ('t0', 30, 0, 0, 185548.427, 1204.8929, 11289600.0, None,
         ((1, 92),)),)),
    40: (1289, (2, 0), (
        ('t0', 45, 0, 0, 924828.184, 15786.915, 16934400.0, 4, ((1, 140),)),
        ('t1', 45, 0, 0, 1001045.59, 15452.6616, 13547520.0, 4,
         ((1, 136),)),
        ('t2', 45, 0, 0, 886505.965, 5340.22212, 11854080.0, 5,
         ((1, 138),)),)),
    41: (1568, None, (
        ('t0', 53, 0, 7, 2278179.06, 6082.87283, 17743488.0, 214,
         ((1, 195),)),
        ('t1', 54, 0, 6, 2138899.23, 5493.46679, 16213120.0, 211,
         ((1, 174),)),
        ('t2', 52, 0, 8, 2559392.54, 6440.93794, 17687040.0, 206,
         ((1, 175),)),)),
    42: (680, None, (
        ('t0', 45, 0, 0, 714831.978, 1561.41442, 9878400.0, None,
         ((1, 135),)),
        ('t1', 45, 0, 0, 954085.167, 3375.711, 17687040.0, None,
         ((1, 140),)),)),
    43: (239, None, (
        ('t0', 26, 0, 19, 308838.068, 1153.59088, 8843520.0, None,
         ((1, 75),)),)),
    44: (1199, None, (
        ('t0', 41, 0, 19, 840532.986, 9688.50021, 15993600.0, None,
         ((1, 100), (2, 14))),
        ('t1', 39, 0, 21, 898582.259, 11441.0704, 18439680.0, None,
         ((1, 135), (2, 1))),
        ('t2', 26, 0, 34, 703156.002, 7299.37024, 16633344.0, None,
         ((1, 55), (2, 11))),)),
    45: (361, None, (
        ('t0', 41, 0, 4, 306954.65, 2185.52291, 16181760.0, None,
         ((1, 129),)),)),
    46: (461, None, (
        ('t0', 15, 0, 45, 908688.097, 30750.527, 4026624.0, None,
         ((1, 48),)),)),
    47: (1302, None, (
        ('t0', 60, 0, 0, 906864.868, 1216.87475, 22579200.0, None,
         ((1, 180),)),
        ('t1', 60, 0, 0, 1288324.22, 1528.0921, 38384640.0, None,
         ((1, 180),)),
        ('t2', 60, 0, 0, 1332209.53, 1664.99314, 22579200.0, None,
         ((1, 180),)),)),
    48: (402, None, (
        ('t0', 60, 0, 0, 706043.627, 12618.2365, 18063360.0, None,
         ((1, 60), (2, 60))),)),
    49: (515, None, (
        ('t0', 60, 0, 0, 762630.651, 15455.9607, 18063360.0, 6,
         ((1, 185),)),)),
    50: (448, None, (
        ('t0', 8, 0, 22, 167142.634, 5655.14175, 2107392.0, None,
         ((1, 25),)),
        ('t1', 24, 0, 6, 344345.735, 14661.2814, 7225344.0, None,
         ((1, 72),)),)),
    51: (1226, None, (
        ('t0', 60, 0, 0, 719083.006, 19894.5271, 22579200.0, None,
         ((1, 180),)),
        ('t1', 60, 0, 0, 727361.218, 18222.9994, 22579200.0, None,
         ((1, 180),)),)),
    52: (532, None, (
        ('t0', 60, 0, 0, 936442.016, 9567.52263, 15805440.0, None,
         ((1, 190),)),)),
    53: (210, None, (
        ('t0', 30, 0, 0, 209283.756, 4106.72865, 10160640.0, 0, ((1, 81),)),)),
    54: (309, None, (
        ('t0', 29, 31, 0, 431533.766, 1970.95952, 7639296.0, None,
         ((1, 87),)),)),
    55: (298, None, (
        ('t0', 5, 0, 25, 456983.024, 18159.0813, 8279040.0, None,
         ((1, 21), (3, 14))),)),
    56: (367, None, (
        ('t0', 11, 0, 49, 440249.166, 5004.40855, 3499776.0, None,
         ((1, 50),)),)),
    57: (208, None, (
        ('t0', 8, 0, 22, 293674.563, 5922.75533, 5117952.0, None,
         ((1, 27), (2, 15))),)),
    58: (517, None, (
        ('t0', 11, 34, 0, 318031.716, 20742.2645, 4139520.0, None,
         ((1, 34),)),
        ('t1', 12, 33, 0, 351963.035, 20168.3023, 3612672.0, None,
         ((1, 37),)),
        ('t2', 12, 33, 0, 563214.456, 20262.3387, 3161088.0, None,
         ((1, 38),)),)),
    59: (928, None, (
        ('t0', 60, 0, 0, 981917.574, 1185.26606, 22579200.0, None,
         ((1, 180),)),
        ('t1', 60, 0, 0, 1107638.97, 2540.40412, 19869696.0, None,
         ((1, 194),)),)),
    60: (429, None, (
        ('t0', 45, 0, 0, 540161.597, 2935.40856, 16934400.0, None,
         ((1, 135),)),)),
    61: (258, None, (
        ('t0', 30, 0, 0, 317457.052, 10427.6487, 7902720.0, None,
         ((1, 89), (2, 2))),)),
    62: (474, None, (
        ('t0', 49, 0, 11, 917005.121, 2736.03048, 18439680.0, None,
         ((1, 147),)),)),
    63: (755, None, (
        ('t0', 45, 0, 0, 618850.187, 21032.5254, 11854080.0, None,
         ((1, 135),)),
        ('t1', 45, 0, 0, 627970.816, 21053.1779, 9878400.0, None,
         ((1, 135),)),)),
    64: (271, None, (
        ('t0', 25, 0, 5, 210079.635, 1380.25763, 9031680.0, None,
         ((1, 86),)),)),
    65: (1534, None, (
        ('t0', 60, 0, 0, 1641360.14, 46880.7171, 22579200.0, None,
         ((1, 184),)),
        ('t1', 60, 0, 0, 1788459.44, 47930.4254, 38384640.0, None,
         ((1, 181),)),
        ('t2', 60, 0, 0, 1617364.11, 45991.4224, 22579200.0, None,
         ((1, 183),)),)),
    66: (317, None, (
        ('t0', 7, 0, 23, 173817.077, 3523.60748, 3462144.0, None,
         ((1, 40),)),)),
    67: (240, None, (
        ('t0', 17, 24, 4, 240276.917, 3992.80755, 6397440.0, None,
         ((1, 52),)),)),
    68: (177, None, (
        ('t0', 19, 11, 0, 139030.005, 4949.95587, 7150080.0, None,
         ((1, 21), (2, 18))),)),
    69: (308, None, (
        ('t0', 27, 0, 3, 267273.421, 12350.7492, 11477760.0, None,
         ((1, 92),)),)),
    70: (864, (0, 0), (
        ('t0', 43, 0, 2, 628827.335, 3618.76357, 11854080.0, 0,
         ((1, 136), (2, 1))),
        ('t1', 45, 0, 0, 776619.585, 3835.75706, 16934400.0, 0,
         ((1, 142),)),)),
    71: (310, None, (
        ('t0', 30, 0, 0, 234374.632, 10675.4827, 12983040.0, 31,
         ((1, 105),)),)),
    72: (170, None, (
        ('t0', 16, 14, 0, 244021.199, 9894.45786, 1705984.0, None,
         ((1, 48),)),)),
    73: (387, None, (
        ('t0', 8, 21, 1, 230115.083, 5327.17345, 2408448.0, None,
         ((1, 30),)),
        ('t1', 4, 25, 1, 251983.871, 9626.35738, 2032128.0, None,
         ((1, 23),)),
        ('t2', 5, 25, 0, 228010.688, 9436.42505, 2069760.0, None,
         ((1, 21),)),)),
    74: (295, None, (
        ('t0', 18, 16, 26, 235993.186, 2985.90632, 6209280.0, None,
         ((1, 51),)),)),
    75: (417, None, (
        ('t0', 21, 0, 24, 448501.211, 1471.12705, 7902720.0, None,
         ((1, 97),)),)),
    76: (547, None, (
        ('t0', 24, 0, 36, 655633.95, 13658.975, 17160192.0, None,
         ((1, 86),)),)),
    77: (938, None, (
        ('t0', 18, 0, 42, 973905.22, 6421.66372, 9219840.0, None,
         ((1, 75),)),
        ('t1', 21, 0, 39, 990749.866, 6045.87753, 23106048.0, None,
         ((1, 88),)),
        ('t2', 0, 60, 0, 0.0, 0.0, 0.0, None, ()),)),
    78: (448, None, (
        ('t0', 41, 0, 4, 479716.86, 20799.5443, 13133568.0, 44,
         ((1, 147),)),)),
    79: (299, None, (
        ('t0', 26, 0, 4, 311987.014, 16946.022, 6849024.0, None,
         ((1, 82),)),)),
    80: (427, None, (
        ('t0', 45, 0, 0, 497060.974, 1098.751, 16934400.0, 0, ((1, 135),)),)),
    81: (175, None, (
        ('t0', 13, 32, 0, 148504.884, 2643.99895, 1386112.0, None,
         ((1, 39),)),)),
    82: (567, None, (
        ('t0', 30, 0, 0, 274975.925, 2987.61914, 6585600.0, None,
         ((1, 92),)),
        ('t1', 30, 0, 0, 319654.558, 3409.78593, 6585600.0, None,
         ((1, 92),)),)),
    83: (1259, None, (
        ('t0', 60, 0, 0, 2635307.07, 64946.2593, 18402048.0, None,
         ((1, 37), (2, 74))),
        ('t1', 60, 0, 0, 1779741.35, 51242.8435, 18063360.0, None,
         ((1, 36), (2, 72))),
        ('t2', 60, 0, 0, 1990766.06, 56781.7483, 22579200.0, None,
         ((1, 142), (2, 19))),)),
    84: (108, None, (
        ('t0', 5, 0, 25, 123616.334, 4084.26008, 3386880.0, None,
         ((1, 9), (3, 5))),)),
    85: (124, None, (
        ('t0', 0, 0, 45, 142792.438, 3178.53425, 301056.0, None,
         ((1, 2), (3, 1))),)),
    86: (450, None, (
        ('t0', 60, 0, 0, 784079.537, 18050.366, 29352960.0, None,
         ((1, 100), (2, 40))),)),
    87: (768, None, (
        ('t0', 59, 0, 1, 1257671.52, 39856.806, 46324992.0, None,
         ((1, 205),)),)),
    88: (526, None, (
        ('t0', 38, 0, 7, 680533.548, 8319.1683, 13999104.0, 39,
         ((1, 144),)),)),
    89: (766, None, (
        ('t0', 32, 0, 13, 614785.171, 17341.3218, 16357376.0, 50,
         ((1, 127),)),
        ('t1', 35, 0, 10, 604281.669, 16865.0731, 22265600.0, 48,
         ((1, 129), (2, 1))),)),
    90: (426, None, (
        ('t0', 8, 15, 7, 127459.867, 4650.46475, 1787520.0, None,
         ((1, 27),)),
        ('t1', 4, 21, 5, 168032.069, 5662.74606, 10235904.0, None,
         ((1, 26), (2, 2))),
        ('t2', 0, 0, 30, 75200.5713, 1826.2269, 451584.0, None,
         ((1, 5), (2, 1))),)),
    91: (237, None, (
        ('t0', 10, 0, 20, 228032.787, 6251.08125, 5607168.0, None,
         ((1, 56),)),)),
    92: (174, None, (
        ('t0', 2, 0, 58, 110019.769, 1757.7521, 1223040.0, None,
         ((1, 13),)),)),
    93: (558, None, (
        ('t0', 30, 0, 0, 503485.048, 30953.8303, 11289600.0, None,
         ((1, 55), (2, 19))),
        ('t1', 30, 0, 0, 511637.17, 31886.5951, 19192320.0, None,
         ((1, 50), (2, 21))),)),
    94: (479, None, (
        ('t0', 44, 0, 1, 541703.57, 11971.1533, 11590656.0, None,
         ((1, 140),)),)),
    95: (627, None, (
        ('t0', 60, 0, 0, 695657.17, 3886.8785, 22579200.0, None,
         ((1, 180),)),)),
    96: (853, None, (
        ('t0', 58, 0, 2, 1091739.12, 2596.15598, 20697600.0, 165,
         ((1, 234),)),)),
    97: (152, None, (
        ('t0', 11, 19, 0, 57897.6492, 1538.82244, 4139520.0, None,
         ((1, 33),)),)),
    98: (209, None, (
        ('t0', 30, 0, 0, 201162.61, 2897.76782, 5707520.0, None,
         ((1, 81),)),)),
    99: (597, None, (
        ('t0', 14, 46, 0, 341416.513, 2526.33624, 3687936.0, None,
         ((1, 43),)),
        ('t1', 14, 46, 0, 342769.625, 2529.59961, 3687936.0, None,
         ((1, 42),)),
        ('t2', 9, 51, 0, 309943.08, 2435.45098, 2370816.0, None,
         ((1, 27),)),)),
    100: (261, None, (
        ('t0', 30, 0, 0, 293821.059, 15931.7184, 7902720.0, None,
         ((1, 91),)),)),
    101: (339, None, (
        ('t0', 45, 0, 0, 462465.561, 2244.25347, 16934400.0, None,
         ((1, 135),)),)),
    102: (1309, None, (
        ('t0', 11, 0, 49, 518733.924, 3128.47739, 4766720.0, None,
         ((1, 71),)),
        ('t1', 7, 0, 53, 501005.989, 2018.06863, 4101888.0, None,
         ((1, 41),)),
        ('t2', 11, 0, 49, 1161121.11, 5887.45312, 8391936.0, None,
         ((1, 84),)),)),
    103: (1095, None, (
        ('t0', 38, 0, 22, 903805.981, 22942.7937, 17498880.0, None,
         ((1, 141),)),
        ('t1', 52, 0, 8, 1101598.33, 29550.9034, 39739392.0, None,
         ((1, 193),)),)),
    104: (122, None, (
        ('t0', 8, 22, 0, 66506.3686, 1799.93361, 3386880.0, 5, ((1, 27),)),)),
    105: (501, None, (
        ('t0', 30, 0, 0, 212597.225, 1918.94211, 9031680.0, None,
         ((1, 66), (2, 3))),
        ('t1', 29, 0, 1, 315382.787, 2591.2484, 8128512.0, None,
         ((1, 77), (2, 3))),)),
    106: (392, None, (
        ('t0', 45, 0, 0, 442435.805, 8566.46067, 16934400.0, None,
         ((1, 140),)),)),
    107: (298, None, (
        ('t0', 30, 0, 0, 417662.502, 10674.9899, 11289600.0, None,
         ((1, 90),)),)),
    108: (358, None, (
        ('t0', 45, 0, 0, 683686.101, 8955.64791, 16934400.0, None,
         ((1, 132), (2, 3))),)),
    109: (310, None, (
        ('t0', 30, 0, 0, 157249.816, 7441.60464, 7902720.0, None,
         ((1, 90),)),)),
    110: (226, None, (
        ('t0', 19, 26, 0, 388742.537, 3386.9028, 7150080.0, None,
         ((1, 60),)),)),
    111: (115, None, (
        ('t0', 0, 0, 45, 24085.8869, 717.156446, 376320.0, None, ((1, 5),)),)),
    112: (629, None, (
        ('t0', 14, 46, 0, 244658.561, 2812.87836, 4214784.0, None,
         ((1, 42),)),
        ('t1', 18, 42, 0, 228871.429, 1731.70918, 4741632.0, None,
         ((1, 54),)),
        ('t2', 11, 49, 0, 207688.257, 2807.22479, 2414720.0, None,
         ((1, 33),)),)),
    113: (240, None, (
        ('t0', 30, 0, 0, 273081.012, 2027.60709, 11665920.0, None,
         ((1, 95),)),)),
    114: (452, None, (
        ('t0', 15, 0, 15, 201268.922, 6983.00632, 7526400.0, None,
         ((1, 23), (2, 9), (3, 7))),
        ('t1', 27, 0, 3, 297758.379, 4644.52447, 7112448.0, None,
         ((1, 38), (2, 5), (3, 4), (4, 6))),)),
    115: (325, None, (
        ('t0', 32, 28, 0, 389443.156, 1192.03059, 12042240.0, None,
         ((1, 96),)),)),
    116: (673, None, (
        ('t0', 32, 0, 28, 650070.176, 2377.52992, 18439680.0, None,
         ((1, 150),)),)),
    117: (229, None, (
        ('t0', 30, 0, 0, 248688.588, 1067.34301, 11289600.0, None,
         ((1, 90),)),)),
    118: (524, None, (
        ('t0', 60, 0, 0, 834823.956, 21617.302, 22579200.0, None,
         ((1, 188),)),)),
    119: (280, None, (
        ('t0', 16, 0, 14, 138772.21, 5120.69836, 7526400.0, None,
         ((1, 63),)),)),
}
