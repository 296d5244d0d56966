"""The serving path: a decoder behind ``ServingEngine.serve``, greedy.

Weights are made from the seed on the device in one jitted call, in the
served types and the layout ``Model.init`` describes, and handed to a
``ServingEngine`` on the paper's cluster. Each unit of the window is one
``serve`` call of ``requests_per_call`` requests with prompts from the
seed, inside a ``bench.serve`` span. ``ServingEngine`` has no warm-up
entry, so set-up warms up with one whole call of the window's shape.
Calls follow each other until ``--seconds`` have passed; the window ends
when the last one returns.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, generate
from bench.harness import now
from bench.ref import qwen2 as ref


@dataclasses.dataclass
class State:
    config: dict
    traffic: dict
    params: object
    prompts: np.ndarray
    engine: object = None
    parts: dict = dataclasses.field(default_factory=dict)


def model_config(config: dict):
    """The program's ``ModelConfig`` set to what the config file states."""
    from repro.configs import get_config
    d, heads = config["hidden_size"], config["num_attention_heads"]
    if config["hidden_act"] != "silu" or config["use_sliding_window"]:
        raise ValueError("the serving path runs SwiGLU decoders with full attention")
    return dataclasses.replace(
        get_config(config["program_arch"]),
        num_layers=config["num_hidden_layers"], d_model=d, num_heads=heads,
        num_kv_heads=config["num_key_value_heads"], head_dim=d // heads,
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        rope_theta=float(config["rope_theta"]), norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=config["tie_word_embeddings"], qkv_bias=True, act="silu",
        norm="rmsnorm", dtype=config["torch_dtype"], family="dense", num_experts=0)


def make_weights(abstract, key):
    """Seeded weights for the ``abstract`` tree, on the device, in one
    jitted call: matrices normal / sqrt(fan_in), the embedding 0.02 N,
    biases 0.1 N, norm scales 1 + 0.1 N."""
    flat, tree = jax.tree_util.tree_flatten_with_path(abstract)

    def build(key):
        out = []
        for i, (where, a) in enumerate(flat):
            name = where[-1].key
            z = jax.random.normal(jax.random.fold_in(key, i), a.shape, jnp.float32)
            if name == "embed":
                z = 0.02 * z
            elif name == "scale":
                z = 1.0 + 0.1 * z
            elif name.startswith("b_"):
                z = 0.1 * z
            else:
                z = z / np.sqrt(a.shape[-2])
            out.append(z.astype(a.dtype))
        return jax.tree_util.tree_unflatten(tree, out)
    return jax.jit(build)(key)


def _call(engine, prompts: np.ndarray, new_tokens: int) -> np.ndarray:
    from repro.serving import Request
    reqs = [Request(i, p, new_tokens) for i, p in enumerate(prompts)]
    engine.serve(reqs)
    return np.stack([np.asarray(r.output, np.int64) for r in reqs])


def setup(config: dict, traffic: dict, seed: int) -> State:
    from repro.core import make_paper_cluster
    from repro.models.model import Model
    from repro.serving import ServingEngine

    t = now()
    cfg = model_config(config)
    abstract, _ = Model(cfg).init(abstract=True)
    key = jnp.asarray(generate.key_words(seed, generate.WEIGHTS))
    params = jax.block_until_ready(make_weights(abstract, key))
    state = State(config, traffic, params, generate.prompts(traffic, config, seed))
    state.engine = ServingEngine(cfg, params, make_paper_cluster(),
                                 max_batch=traffic["max_batch"])
    state.parts["weights"], t = now() - t, now()
    _call(state.engine, state.prompts[0], traffic["new_tokens"])    # warm-up call
    state.parts["warm-up"] = now() - t
    return state


def serve(state: State, traffic: dict, seconds: float, rec) -> list:
    n_req, p, n = traffic["requests_per_call"], traffic["prompt_len"], traffic["new_tokens"]
    per_call = flops.qwen2_serve_call_flops(state.config, n_req, p, n)
    units = []
    t_open = rec.open_window()
    k = 0
    while not units or units[-1]["end"] - t_open < seconds:
        rec.unit = k
        start = now()
        with rec.span("bench.serve"):
            out = _call(state.engine, state.prompts[k % len(state.prompts)], n)
        end = now()
        units.append(dict(due=start, start=start, end=end, requests=n_req, items=n_req * n,
                          positions=n_req * (p + n - 1), flops=per_call,
                          prompt=k % len(state.prompts), out=out,
                          failed=out.shape != (n_req, n)))
        rec.unit_done(end)
        k += 1
    rec.close_window(units[-1]["end"])
    return units


def check(state: State, units: list, seed: int, control: bool = False) -> dict:
    """``logit_gap``: over a sample of the window's requests drawn from
    the seed, the widest gap by which a served token's reference logit
    lies below the reference's best at its position. With ``control``,
    also the widest such gap of the tokens that the reference in float8
    ranks first."""
    state.engine = None                           # the program's state goes
    tr = state.traffic
    n_req, p, n = tr["requests_per_call"], tr["prompt_len"], tr["new_tokens"]
    picks = generate.sample(len(units) * n_req, tr["sample"], seed)
    prompts = np.stack([state.prompts[units[i // n_req]["prompt"]][i % n_req] for i in picks])
    served = np.stack([units[i // n_req]["out"][i % n_req] for i in picks])
    if served.shape != (len(picks), n) or served.min() < 0 \
            or served.max() >= state.config["vocab_size"]:
        return {"logit_gap": float("inf")}
    tokens = np.concatenate([prompts, served[:, :-1]], axis=1).astype(np.int32)
    served = served.astype(np.int32)
    if not control:
        return {"logit_gap": float(ref.gaps(state.config, state.params, tokens, served, p).max())}
    gap, low = ref.gaps(state.config, state.params, tokens, served, p, quant="fp8")
    return {"logit_gap": float(gap.max()), "control.logit_gap": float(low.max())}
