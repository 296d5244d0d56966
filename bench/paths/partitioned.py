"""The partitioned path: MobileNetV2 cut into stages by the DP planner and
served through ``DistributedInference.infer``.

The harness builds the executor it hands to ``DistributedInference``: the
program's ``run_range`` over the stage's leaves, ended by
``block_until_ready`` (a stage's activation is complete before it is
handed to the next stage), inside a ``bench.executor`` span. Each request
is one ``infer`` call on a host batch whose logits are brought back to the
host, inside a ``bench.request`` span.

Closed mixes send the next request when the last one's logits are on the
host, until ``--seconds`` have passed. Poisson mixes send each request at
its due time (or as soon as the one before it is done: one request in
flight, FIFO), and the window ends when the last one is done.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, generate
from bench.harness import now
from bench.ref import mobilenetv2 as ref


@dataclasses.dataclass
class State:
    config: dict
    traffic: dict
    seed: int
    params: list
    pool: np.ndarray
    infer: object = None
    span: object = None
    parts: dict = dataclasses.field(default_factory=dict)


def program_leaves(config: dict, params: list):
    """The program's 141 leaves holding the benchmark's parameters."""
    from repro.configs import mobilenetv2 as C
    from repro.models import mobilenetv2 as M

    program = dict(inverted_residual_setting=[list(r) for r in C.INVERTED_RESIDUAL_SETTING],
                   stem_channels=C.INPUT_CHANNELS, last_channels=C.LAST_CHANNELS,
                   num_classes=C.NUM_CLASSES)
    stated = {k: config[k] for k in program}
    if program != stated:
        raise ValueError(f"the program's MobileNetV2 is {program}, the config states {stated}")
    built = {}

    def structure(key):
        built["leaves"] = M.build_mobilenetv2(key)
        return [leaf.params for leaf in built["leaves"]]

    shapes = jax.eval_shape(structure, jax.random.PRNGKey(0))
    holders = [i for i, s in enumerate(shapes) if s]
    if len(holders) != len(params):
        raise ValueError(f"{len(holders)} leaves hold parameters, the reference has {len(params)}")
    leaves = list(built["leaves"])
    for i, p in zip(holders, params):
        want = {n: (s.shape, s.dtype) for n, s in shapes[i].items()}
        got = {n: (a.shape, a.dtype) for n, a in p.items()}
        if want != got:
            raise ValueError(f"leaf {leaves[i].name}: program {want}, reference {got}")
        leaves[i] = dataclasses.replace(leaves[i], params=p)
    return leaves


def setup(config: dict, traffic: dict, seed: int) -> State:
    from repro.core import ModelPartitioner, make_paper_cluster
    from repro.core.pipeline import DistributedInference
    from repro.models.graph import mobilenetv2_graph
    from repro.models.mobilenetv2 import run_range

    # the configuration's float32 at highest precision: XLA's default on a
    # TPU would round every convolution's operands to bfloat16
    jax.config.update("jax_default_matmul_precision", config["precision"])
    t = now()
    key = jnp.asarray(generate.key_words(seed, generate.WEIGHTS))
    params = jax.block_until_ready(ref.init_params(config, key))
    parts = {"weights": now() - t}
    t = now()
    leaves = program_leaves(config, params)
    parts["leaves"], t = now() - t, now()
    state = State(config, traffic, seed, params, generate.images(traffic, config, seed),
                  span=lambda name: contextlib.nullcontext(), parts=parts)
    parts["inputs"], t = now() - t, now()

    def executor(lo, hi, x, residual):
        with state.span("bench.executor"):
            return jax.block_until_ready(run_range(leaves, lo, hi, x, residual))

    d = DistributedInference(make_paper_cluster(), ModelPartitioner(mobilenetv2_graph()),
                             method="planner", batch=traffic["batch"], executor=executor)
    state.infer = d.infer
    parts["plan"], t = now() - t, now()
    np.asarray(state.infer(state.pool[0]))       # warm every op shape of the window
    parts["warm-up"] = now() - t
    return state


def _wait_until(t: float) -> None:
    while True:
        left = t - now()
        if left <= 0:
            return
        time.sleep(left - 1e-3 if left > 2e-3 else 0)


def serve(state: State, traffic: dict, seconds: float, rec) -> list:
    state.span = rec.span
    batch = traffic["batch"]
    per_request = batch * flops.mobilenetv2_flops_per_image(state.config)
    open_loop = traffic["kind"] == "poisson"
    offsets = generate.arrivals(traffic, seconds, state.seed)
    units = []
    t_open = rec.open_window()
    i = 0
    while True:
        if open_loop:
            if i == len(offsets):
                break
            due = t_open + offsets[i]
        elif units and units[-1]["end"] - t_open >= seconds:
            break
        else:
            due = now()
        idle = now() <= due
        _wait_until(due)
        rec.unit = i
        start = now()
        with rec.span("bench.request"):
            out = np.asarray(state.infer(state.pool[i % len(state.pool)]))
        end = now()
        units.append(dict(due=due, start=start, end=end, requests=1, items=batch,
                          flops=per_request, input=i % len(state.pool), out=out,
                          idle_before=idle and open_loop))
        rec.unit_done(end)
        i += 1
    rec.close_window(units[-1]["end"])
    return units


def rel_err(out: np.ndarray, reference: np.ndarray) -> float:
    """max |out - reference| / max |reference|; inf for a wrong shape or a
    value that is not finite."""
    if out.shape != reference.shape or not np.isfinite(out).all():
        return float("inf")
    return float(np.abs(out - reference).max() / np.abs(reference).max())


def check(state: State, units: list, seed: int, control: bool = False) -> dict:
    """``logit_rel_err``: over a sample of the window's requests drawn from
    the seed, the largest relative error of a request's logits against the
    float32 reference. With ``control``, also the same number of the
    reference computed in three bfloat16 passes (``high``)."""
    state.infer = None                            # the program's state goes
    batch = state.traffic["batch"]
    picks = generate.sample(len(units), state.traffic["sample"], seed)
    images = np.concatenate([state.pool[units[i]["input"]] for i in picks])
    expect = np.asarray(ref.forward(state.config, state.params, images))
    got = [units[i]["out"] for i in picks]
    readings = {"logit_rel_err": max(
        rel_err(y, expect[j * batch:(j + 1) * batch]) for j, y in enumerate(got))}
    if control:
        low = np.asarray(ref.forward(state.config, state.params, images, "high"))
        readings["control.logit_rel_err"] = max(
            rel_err(low[j * batch:(j + 1) * batch], expect[j * batch:(j + 1) * batch])
            for j in range(len(picks)))
    return readings
