"""Batched serving engine with AMP4EC scheduling.

Real greedy decoding (JAX) over model replicas "deployed" on simulated edge
nodes: a model that ``can_prefill`` (dense or MoE decoders, Mamba-2, and
hybrids of Mamba-2 and attention) takes a group's prompt in one jitted
prefill (``Model.prefill``), every other family steps ``decode_step``
through it one position at a time, and each new token is one
``decode_step``. The AMP4EC TaskScheduler (NSA) routes each batch to a
replica, and node time is charged via a FLOPs-based edge cost model, so
the serving metrics (TTFT, per-token latency, throughput, load
distribution) reflect the paper's scheduling behaviour while numerics
stay real.

The batcher groups requests by prompt length (uniform-position batches match
the scalar-position cache layout used by the production decode path).
"""

from __future__ import annotations

import dataclasses
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.cluster import EdgeCluster
from repro.core.monitor import ResourceMonitor
from repro.core.scheduler import SCHEDULING_OVERHEAD_MS, TaskRequirements, TaskScheduler
from repro.models.model import Model, is_recurrent_state
from repro.utils import obs

EDGE_FLOPS_PER_CPU = 5e9  # effective flop/s per 1.0 edge CPU (serving cost model)


# serve()'s metrics priced by the edge cost model, not timed
SIMULATED = ("avg_latency_ms", "p99_latency_ms", "avg_ttft_ms", "tokens_per_s")


def measured_ms(snapshot: dict) -> Dict[str, Optional[float]]:
    """Host-clock times of ``serve`` calls, from a snapshot of the recorder
    (``repro.utils.obs``): the mean time to a group's first token on the
    host (``amp4ec.prompt``), the mean gap between its generated tokens
    (``amp4ec.generate`` over new tokens less one) and the mean time to
    route a group (``amp4ec.schedule``); and ``prefill_share``, the share
    of prompt positions taken by a prefill rather than stepped. None where
    nothing was recorded."""
    spans = snapshot["spans"]

    def mean(name):
        found = [s.end - s.start for s in spans if s.name == name]
        return sum(found) / len(found) * 1e3 if found else None

    groups = {s.span_id: s for s in spans if s.name == "amp4ec.group"}
    generate = [s for s in spans if s.name == "amp4ec.generate" and s.root_id in groups]
    gaps = sum(groups[s.root_id].attrs["new_tokens"] - 1 for s in generate)
    itl = sum(s.end - s.start for s in generate) / gaps * 1e3 if gaps > 0 else None
    prompts = [s.attrs for s in spans if s.name == "amp4ec.prompt"]
    prefilled = sum(a["prefilled"] for a in prompts)
    positions = prefilled + sum(a["stepped"] for a in prompts)
    return dict(ttft_ms=mean("amp4ec.prompt"), itl_ms=itl, route_ms=mean("amp4ec.schedule"),
                prefill_share=prefilled / positions if positions else None)


COUNTS = ("routed_here", "expert_load_max", "dropped")


def measured_counts(snapshot: dict) -> Dict[str, Optional[int]]:
    """The MoE counters of ``serve`` calls, from a snapshot of the recorder:
    over the ``amp4ec.prompt`` and ``amp4ec.generate`` spans, the routed
    assignments the held experts computed (``routed_here``), the most that
    one held expert took in one layer of one pass (``expert_load_max``) and
    the assignments dropped (``dropped``). None where no span carries them
    (no MoE layer)."""
    found = [s.attrs for s in snapshot["spans"]
             if s.name in ("amp4ec.prompt", "amp4ec.generate") and COUNTS[0] in s.attrs]
    if not found:
        return dict.fromkeys(COUNTS)
    return dict(routed_here=sum(a["routed_here"] for a in found),
                expert_load_max=max(a["expert_load_max"] for a in found),
                dropped=sum(a["dropped"] for a in found))


def cache_len(prompt_len: int, new_tokens: int) -> int:
    """Cache slots a group decodes into: every position, and one spare."""
    return prompt_len + new_tokens + 1


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: np.ndarray            # (P,) int32
    max_new_tokens: int
    arrival_ms: float = 0.0
    # filled by the engine:
    output: Optional[np.ndarray] = None
    node_id: str = ""
    ttft_ms: float = 0.0
    finish_ms: float = 0.0


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, cluster: EdgeCluster,
                 max_batch: int = 8):
        self.cfg = cfg
        self.model = Model(cfg)
        self.params = params
        self.cluster = cluster
        self.monitor = ResourceMonitor(cluster)
        self.scheduler = TaskScheduler()
        self.max_batch = max_batch
        self._decode_jit = jax.jit(self.model.decode_step)
        self._prefill_jit = jax.jit(self.model.prefill, static_argnums=2)
        self._flops_per_token = 2.0 * self.model.param_count(params)

    def _cache_bytes(self, batch: int, slots: int) -> Tuple[int, int]:
        """Device bytes of the decode cache of a group: all of it, and its
        recurrent states and conv windows."""
        cache, _ = self.model.init_cache(batch, slots, abstract=True)
        size = {name: a.size * a.dtype.itemsize for name, a in cache.items()}
        return (sum(size.values()),
                sum(n for name, n in size.items() if is_recurrent_state(name)))

    # --- batching -------------------------------------------------------------

    def _buckets(self, requests: List[Request]) -> List[List[Request]]:
        by_len: Dict[Tuple[int, int], List[Request]] = defaultdict(list)
        for r in requests:
            by_len[(len(r.prompt), r.max_new_tokens)].append(r)
        groups = []
        for key, rs in sorted(by_len.items()):
            for i in range(0, len(rs), self.max_batch):
                groups.append(rs[i:i + self.max_batch])
        return groups

    # --- generation -------------------------------------------------------------

    def _generate_group(self, group: List[Request]) -> np.ndarray:
        """Real greedy decode for a uniform-length group. Returns (B, N).

        ``amp4ec.prompt`` runs until the first generated token is on the
        host, so the device backlog of the prompt's work falls inside it;
        it holds one ``amp4ec.prefill`` (its ``rows``, sequences a block,
        and ``blocks``) where the model ``can_prefill``, else the
        teacher-forced steps. ``amp4ec.generate`` runs from there
        until the last token is on the host. With the recorder on, each of
        the two carries the MoE counters of its passes (``COUNTS``)."""
        B = len(group)
        P = len(group[0].prompt)
        N = group[0].max_new_tokens
        tokens = jnp.asarray(np.stack([r.prompt for r in group]), jnp.int32)
        out = []
        with obs.span("amp4ec.prompt") as prompt:
            if self.model.can_prefill:
                attrs = {}
                if obs.enabled():
                    rows = self.model.prefill_rows(B, P)
                    attrs = dict(rows=rows, blocks=B // rows)
                with obs.span("amp4ec.prefill", **attrs):
                    logits, cache = self._prefill_jit(self.params, tokens, cache_len(P, N))
                tok = self._sample(logits, out) if N else None
                prompt.set(prefilled=B * P, stepped=0)
            else:
                tok, cache = self._teacher_force(tokens, N, out)
                prompt.set(prefilled=0, stepped=B * (P - 1 + bool(N)))
            cache = self._take_counts(prompt, cache)
        with obs.span("amp4ec.generate") as generate:
            for _ in range(N - 1):
                tok, cache = self._next_token(tok, cache, out)
            self._take_counts(generate, cache)
        return np.stack(out, axis=1) if out else np.zeros((B, 0), np.int32)

    @staticmethod
    def _take_counts(span, cache):
        """With the recorder on, put the MoE counts that ``cache`` carries on
        ``span`` and return the cache with them zeroed; else return it as it
        is, with nothing read from the device."""
        if not obs.enabled() or "moe_counts" not in cache:
            return cache
        span.set(**dict(zip(COUNTS, (int(c) for c in np.asarray(cache["moe_counts"])))))
        return dict(cache, moe_counts=jnp.zeros((3,), jnp.int32))

    def _teacher_force(self, tokens, N: int, out: list):
        """The prompt one ``decode_step`` a position, and the first token;
        returns it on the device and the cache."""
        cfg = self.cfg
        B, P = tokens.shape
        cache, _ = self.model.init_cache(B, cache_len(P, N))
        if cfg.family in ("audio", "vlm"):
            from repro.data.pipeline import frontend_stub
            frames = cfg.num_frames if cfg.family == "audio" else cfg.num_image_tokens
            mem = jnp.asarray(frontend_stub(cfg.family, B, frames, cfg.d_model))
            cache = self.model.fill_cross_cache(self.params, cache, mem)
        for t in range(P - 1):
            _, cache = self._step(tokens[:, t], cache)
        tok = tokens[:, P - 1]
        if N:
            tok, cache = self._next_token(tok, cache, out)
        return tok, cache

    def _step(self, tok, cache):
        with obs.span("amp4ec.step"):
            return self._decode_jit(self.params, tok, cache)

    def _next_token(self, tok, cache, out: list):
        """One decode step and its greedy token, which is appended to
        ``out`` on the host; returns the token on the device and the
        cache."""
        logits, cache = self._step(tok, cache)
        return self._sample(logits, out), cache

    @staticmethod
    def _sample(logits, out: list):
        """The greedy token of ``logits``, appended to ``out`` on the host;
        returns it on the device."""
        with obs.span("amp4ec.sample"):
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(np.asarray(tok))
        return tok

    # --- serving ------------------------------------------------------------------

    def serve(self, requests: List[Request]) -> dict:
        """Process all requests; returns aggregate metrics. The latencies,
        ``avg_ttft_ms`` and ``tokens_per_s`` (the keys of ``SIMULATED``) are
        simulated edge time from ``EDGE_FLOPS_PER_CPU``, not a clock; with
        the recorder on, ``measured_ms`` gives the host clock's.

        All request groups are submitted at the current simulated time (a
        closed batch, like the paper's request batches); the NSA sees the
        accumulating in-flight queue per node, and completions feed the
        performance history after the batch.
        """
        clock = self.cluster.clock
        t0 = clock.now_ms
        for r in requests:
            r.arrival_ms = max(r.arrival_ms, t0)
        groups = self._buckets(requests)
        done: List[tuple] = []
        with obs.span("amp4ec.serve", requests=len(requests)):
            for group in groups:
                P = len(group[0].prompt)
                N = group[0].max_new_tokens
                with obs.span("amp4ec.schedule"):
                    stats = self.monitor.poll(force=True)
                    node_id = self.scheduler.select_node(
                        [s for s in stats.values() if s.online], TaskRequirements())
                    if node_id is None:
                        node_id = min(self.cluster.online_nodes(),
                                      key=lambda n: n.busy_until_ms).node_id
                attrs = {}
                if obs.enabled():
                    total, state = self._cache_bytes(len(group), cache_len(P, N))
                    attrs = dict(batch=len(group), prompt_len=P, new_tokens=N,
                                 cache_len=cache_len(P, N), node=node_id,
                                 cache_bytes=total, state_bytes=state,
                                 requests=[r.request_id for r in group])
                with obs.root("amp4ec.group", **attrs):
                    out = self._generate_group(group)

                node = self.cluster.nodes[node_id]
                ms_per_token = (self._flops_per_token * len(group)
                                / (EDGE_FLOPS_PER_CPU * node.profile.cpu) * 1e3)
                start = max(t0 + SCHEDULING_OVERHEAD_MS, node.busy_until_ms)
                ttft = start + P * ms_per_token
                finish = start + (P + N) * ms_per_token
                node.busy_until_ms = finish
                node.task_count += 1
                node.cpu_busy_ms += finish - start
                done.append((node_id, finish - start))
                for i, r in enumerate(group):
                    r.output = out[i]
                    r.node_id = node_id
                    r.ttft_ms = ttft - t0
                    r.finish_ms = finish
        for node_id, dur in done:
            self.scheduler.task_completed(node_id, dur)
        clock.now_ms = max([clock.now_ms] + [r.finish_ms for r in requests])

        lat = [r.finish_ms - r.arrival_ms for r in requests]
        new_tokens = sum(r.max_new_tokens for r in requests)
        makespan = max(r.finish_ms for r in requests) - t0
        per_node = defaultdict(int)
        for r in requests:
            per_node[r.node_id] += 1
        return dict(
            num_requests=len(requests),
            avg_latency_ms=float(np.mean(lat)),
            p99_latency_ms=float(np.percentile(lat, 99)),
            avg_ttft_ms=float(np.mean([r.ttft_ms for r in requests])),
            tokens_per_s=1000.0 * new_tokens / max(makespan, 1e-9),
            requests_per_node=dict(per_node),
            scheduler=self.scheduler.metrics(),
        )
