"""The program's span recorder (``repro.utils.obs``) and the spans that
the planner, the partitioned path and the serving engine leave behind
when it is on."""

import sys
import threading
import time

import numpy as np
import pytest

from repro.utils import obs


@pytest.fixture
def recording():
    """The process's recorder, on for one test and off after it."""
    obs.enable()
    try:
        yield
    finally:
        obs.disable()


def _named(snap, name):
    return [s for s in snap["spans"] if s.name == name]


def test_off_records_nothing_and_returns_the_shared_null_context():
    rec = obs.Recorder()
    with rec.span("amp4ec.a", x=1) as sp:
        sp.set(y=2)
        with rec.root("amp4ec.b"):
            pass
    assert rec.span("amp4ec.a") is obs.NULL and rec.root("amp4ec.b") is obs.NULL
    assert not rec.enabled()
    assert rec.snapshot() == {"spans": []}
    assert obs.span("amp4ec.a", x=1) is obs.NULL      # the process's recorder is off
    assert not obs.enabled()


def test_on_records_parent_root_attrs_and_counters():
    rec = obs.Recorder()
    rec.enable()
    assert rec.enabled()
    with rec.span("amp4ec.serve"):
        with rec.span("amp4ec.schedule", node="edge-1"):
            pass
        with rec.root("amp4ec.group", batch=4) as sp:
            with rec.span("amp4ec.step"):
                pass
            sp.set(stages=3)
    snap = rec.snapshot()
    by = {s.name: s for s in snap["spans"]}
    serve, schedule, group, step = (by[n] for n in ("amp4ec.serve", "amp4ec.schedule",
                                                    "amp4ec.group", "amp4ec.step"))
    assert serve.parent_id is None and serve.root_id == serve.span_id
    assert schedule.parent_id == serve.span_id and schedule.root_id == serve.span_id
    assert schedule.attrs == {"node": "edge-1"}
    # a root span is its requests' identifier, though it has a parent
    assert group.parent_id == serve.span_id and group.root_id == group.span_id
    assert step.parent_id == group.span_id and step.root_id == group.span_id
    assert group.attrs == {"batch": 4, "stages": 3}
    assert len({s.span_id for s in snap["spans"]}) == 4


def test_spans_are_timed_on_the_perf_counter_clock():
    rec = obs.Recorder()
    rec.enable()
    t0 = time.perf_counter()
    with rec.span("amp4ec.outer"):
        with rec.span("amp4ec.inner"):
            time.sleep(0.01)
    t1 = time.perf_counter()
    inner, outer = rec.snapshot()["spans"]
    assert t0 <= outer.start <= inner.start < inner.end <= outer.end <= t1
    assert inner.end - inner.start >= 0.01


def test_disable_keeps_the_records_and_enable_starts_afresh():
    rec = obs.Recorder()
    rec.enable()
    with rec.span("amp4ec.a"):
        pass
    rec.disable()
    with rec.span("amp4ec.b"):
        pass
    assert not rec.enabled()
    assert [s.name for s in rec.snapshot()["spans"]] == ["amp4ec.a"]
    rec.enable()
    assert rec.snapshot() == {"spans": []}


def test_a_span_closes_on_an_exception():
    rec = obs.Recorder()
    rec.enable()
    with pytest.raises(ValueError):
        with rec.span("amp4ec.a"):
            raise ValueError
    with rec.span("amp4ec.b"):
        pass
    a, b = rec.snapshot()["spans"]
    assert b.parent_id is None and b.root_id == b.span_id


def test_threads_keep_their_own_parents_and_lose_no_count():
    rec = obs.Recorder()
    rec.enable()
    workers, rounds = 16, 400

    def work():
        for _ in range(rounds):
            with rec.root("amp4ec.request"):
                with rec.span("amp4ec.stage"):
                    pass

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(before)
    assert not any(t.is_alive() for t in threads)
    snap = rec.snapshot()
    roots = {s.span_id for s in snap["spans"] if s.name == "amp4ec.request"}
    stages = [s for s in snap["spans"] if s.name == "amp4ec.stage"]
    assert len(roots) == len(stages) == workers * rounds
    assert all(s.parent_id == s.root_id and s.parent_id in roots for s in stages)
    assert len({s.parent_id for s in stages}) == len(stages)


# --- what the program leaves behind ------------------------------------------

@pytest.mark.parametrize("arch", ["qwen2.5-3b", "mamba2-130m", "recurrentgemma-9b",
                                  "granite-4.0-h-small"])
def test_serve_leaves_prompt_generate_steps_and_samples(arch, recording):
    """A dense decoder's, a Mamba-2 stack's or a Mamba-2 / attention
    hybrid's prompt is one ``amp4ec.prefill`` (one block of both rows); an
    RG-LRU hybrid's is P - 1 teacher-forced steps and the first token's
    step. ``amp4ec.group`` gives the cache's bytes and those of its
    recurrent state; an MoE model's phases carry its counters beside."""
    import jax
    from repro.configs import get_config
    from repro.core.cluster import make_paper_cluster
    from repro.models.model import Model, is_recurrent_state
    from repro.serving import Request, ServingEngine
    from repro.serving.engine import COUNTS, measured_ms

    cfg = get_config(arch).reduced()
    params, _ = Model(cfg).init(jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, make_paper_cluster(), max_batch=2)
    prefills = engine.model.can_prefill
    assert prefills == (arch != "recurrentgemma-9b")
    P, N = 5, 4
    reqs = [Request(i, np.arange(1, P + 1, dtype=np.int32), N) for i in range(4)]
    obs.enable()                               # the set-up's spans go
    engine.serve(reqs)
    snap = obs.snapshot()
    groups = _named(snap, "amp4ec.group")
    assert len(groups) == 2 and len(_named(snap, "amp4ec.serve")) == 1
    assert len(_named(snap, "amp4ec.schedule")) == 2
    cache, _ = engine.model.init_cache(2, P + N + 1)
    state = sum(a.nbytes for name, a in cache.items() if is_recurrent_state(name))
    assert groups[0].attrs == dict(batch=2, prompt_len=P, new_tokens=N, cache_len=P + N + 1,
                                   node=reqs[0].node_id, requests=[0, 1],
                                   cache_bytes=sum(a.nbytes for a in jax.tree.leaves(cache)),
                                   state_bytes=state)
    assert (state > 0) == (arch != "qwen2.5-3b")
    for g in groups:
        mine = [s for s in snap["spans"] if s.root_id == g.span_id and s is not g]
        count = {n: sum(s.name == n for s in mine)
                 for n in ("amp4ec.prompt", "amp4ec.generate", "amp4ec.prefill",
                           "amp4ec.step", "amp4ec.sample")}
        assert count == {"amp4ec.prompt": 1, "amp4ec.generate": 1,
                         "amp4ec.prefill": int(prefills),
                         "amp4ec.step": N - 1 if prefills else P + N - 1,
                         "amp4ec.sample": N}
        prompt, = (s for s in mine if s.name == "amp4ec.prompt")
        generate, = (s for s in mine if s.name == "amp4ec.generate")
        assert g.start <= prompt.start < prompt.end <= generate.start < generate.end <= g.end
        counters = {k: v for k, v in prompt.attrs.items() if k not in COUNTS}
        assert counters == (dict(prefilled=2 * P, stepped=0) if prefills
                            else dict(prefilled=0, stepped=2 * P))
        assert (set(COUNTS) <= set(prompt.attrs)) == bool(cfg.num_experts)
        # the first token's sample ends the prompt phase
        assert sum(prompt.start <= s.start and s.end <= prompt.end
                   for s in mine if s.name == "amp4ec.sample") == 1
        # before the first sample: the prefill, or P - 1 teacher-forced
        # steps and the first token's step
        first = min(s.start for s in mine if s.name == "amp4ec.sample")
        before = [s.name for s in mine if s.end <= first and s.name != "amp4ec.prompt"]
        assert before == (["amp4ec.prefill"] if prefills else ["amp4ec.step"] * P)
        for s in mine:
            if s.name == "amp4ec.prefill":
                assert s.attrs == dict(rows=2, blocks=1)
    t = measured_ms(snap)
    group_ms = sum(g.end - g.start for g in groups) / 2 * 1e3
    assert 0 < t["route_ms"] and 0 < t["itl_ms"]
    assert 0 < t["ttft_ms"] + (N - 1) * t["itl_ms"] <= group_ms
    assert t["prefill_share"] == (1.0 if prefills else 0.0)


def test_moe_counters_count_what_the_reference_routes_to_the_held_experts(recording):
    """DeepSeek-V2's block at tiny widths (4 of 8 routed experts held):
    ``routed_here`` on ``amp4ec.prompt`` and ``amp4ec.generate`` is the
    number of assignments the reference routes to the held experts at the
    positions each phase passes through, and ``dropped`` is 0; with the
    recorder off, the spans carry nothing."""
    from pathlib import Path
    import jax
    import jax.numpy as jnp
    sys.path[:0] = [str(Path(__file__).resolve().parents[1])]
    from bench import generate, harness
    from bench.paths import serving_moe
    from bench.ref import deepseek_v2 as ref
    from repro.core.cluster import make_paper_cluster
    from repro.models.model import Model
    from repro.serving import Request, ServingEngine
    from repro.serving.engine import measured_counts

    config = dict(harness.data("configs", "deepseek-v2-ep8"), hidden_size=64,
                  intermediate_size=96, num_hidden_layers=3, num_attention_heads=4,
                  num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
                  qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                  moe_intermediate_size=16, n_group=4, topk_group=2, num_experts_per_tok=3,
                  n_routed_experts=4, first_routed_expert=2, vocab_size=500,
                  torch_dtype="float32",
                  published=dict(num_hidden_layers=60, n_routed_experts=8))
    cfg = serving_moe.model_config(config)
    abstract, _ = Model(cfg).init(abstract=True)
    params = serving_moe.make_weights(abstract, jnp.asarray(generate.key_words(8, 3)))
    engine = ServingEngine(cfg, params, make_paper_cluster(), max_batch=2)
    P, N = 9, 4
    prompts = generate.rng(9, 2).integers(0, 500, (2, P)).astype(np.int32)
    reqs = [Request(i, prompts[i], N) for i in range(2)]
    engine.serve(reqs)
    snap = obs.snapshot()
    prompt, = _named(snap, "amp4ec.prompt")
    gen, = _named(snap, "amp4ec.generate")
    tokens = np.concatenate([prompts, np.stack([r.output for r in reqs])[:, :-1]], 1)
    with jax.default_matmul_precision("highest"):
        chosen = np.asarray(ref.routes(config, params, tokens, 0, P + N - 1))
    held = (chosen >= 2) & (chosen < 6)                  # (layers, n, positions, k)
    assert prompt.attrs["routed_here"] == held[:, :, :P].sum() > 0
    assert gen.attrs["routed_here"] == held[:, :, P:].sum() > 0
    assert prompt.attrs["dropped"] == gen.attrs["dropped"] == 0
    assert 0 < gen.attrs["expert_load_max"] <= 2 < prompt.attrs["expert_load_max"]
    counts = measured_counts(snap)
    assert counts == dict(routed_here=held.sum(), dropped=0,
                          expert_load_max=prompt.attrs["expert_load_max"])
    obs.disable()
    engine.serve([Request(9, prompts[0], N)])
    assert measured_counts(obs.snapshot()) == counts          # nothing new recorded


def test_prefill_share_counts_positions_over_every_prompt():
    from repro.serving.engine import measured_ms

    def prompt(i, prefilled, stepped):
        return obs.Span("amp4ec.prompt", 0.0, 1.0, i, None, i,
                        dict(prefilled=prefilled, stepped=stepped))

    assert measured_ms({"spans": []})["prefill_share"] is None
    assert measured_ms({"spans": [prompt(1, 0, 0)]})["prefill_share"] is None
    snap = {"spans": [prompt(1, 96, 0), prompt(2, 0, 32)]}
    assert measured_ms(snap)["prefill_share"] == 0.75


def test_infer_leaves_one_stage_per_partition_under_one_root(recording):
    from repro.core import ModelPartitioner, make_paper_cluster
    from repro.core.pipeline import DistributedInference
    from repro.models.graph import mobilenetv2_graph

    d = DistributedInference(make_paper_cluster(), ModelPartitioner(mobilenetv2_graph()),
                             method="planner", use_cache=True,
                             executor=lambda lo, hi, x, res: (x + (hi - lo), res))
    plans = _named(obs.snapshot(), "amp4ec.plan")
    assert len(plans) == 1
    assert plans[0].attrs["stages"] == len(d.plan.partitions) > 1
    assert plans[0].attrs["nodes"] == 3 and plans[0].attrs["mode"] in ("exhaustive", "dp")
    obs.enable()
    d.infer(np.zeros(2), signature="a")
    d.infer(np.zeros(2), signature="a")       # served from the result cache
    snap = obs.snapshot()
    infers = _named(snap, "amp4ec.infer")
    stages = _named(snap, "amp4ec.stage")
    parts = d.plan.partitions
    assert len(infers) == 2 and len(stages) == len(parts)
    assert all(s.root_id == infers[0].span_id == s.parent_id for s in stages)
    assert [(s.attrs["stage"], s.attrs["lo"], s.attrs["hi"], s.attrs["node"]) for s in stages] \
        == [(p.index, p.lo, p.hi, d.placement[p.index]) for p in parts]
    obs.disable()
    d.infer(np.zeros(2), signature="b")       # off: nothing more is recorded
    assert len(obs.snapshot()["spans"]) == len(snap["spans"])


def test_plan_partial_is_a_plan_span(recording):
    from repro.core import make_paper_cluster
    from repro.core.planner import PartitionPlanner, node_views_from_cluster
    from repro.models.graph import mobilenetv2_graph

    graph = mobilenetv2_graph()
    planner = PartitionPlanner(graph)
    views = node_views_from_cluster(make_paper_cluster())
    res = planner.plan(views)
    obs.enable()
    planner.plan_partial(views, res.cuts, res.assignment, max_moves=1)
    plan, = _named(obs.snapshot(), "amp4ec.plan")
    assert plan.attrs == {"mode": "partial", "nodes": len(views), "stages": res.stages}
