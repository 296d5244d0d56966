"""The serving path of a latent-attention MoE decoder (DeepSeek-V2): one
chip's share of an expert-parallel deployment behind ``ServingEngine.serve``,
greedy.

The configuration file states the published model and the share held here:
``n_routed_experts`` experts of each MoE layer from
``first_routed_expert``, of the ``published`` count that the router scores.
Weights are made from the seed on the device leaf by leaf, and a leaf
stacked over layers slice by slice into its place, so that no temporary
of a whole expert stack is ever held beside the weights. Each unit of the
window is one ``serve`` call of ``requests_per_call`` requests with prompts
from the seed, inside a ``bench.serve`` span; set-up warms up with one
whole call of the window's shape. The check compares the served tokens of
a sample of the window's requests with ``bench/ref/deepseek_v2.py``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops_mla, generate
from bench.harness import now
from bench.paths.serving import _call
from bench.ref import deepseek_v2 as ref

# norm scales of the served tree, made as 1 + 0.1 N
NORM_SCALES = ("scale", "q_norm", "kv_norm")


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
    rs = config["rope_scaling"]
    if (config["hidden_act"], config["scoring_func"], config["topk_method"], rs["type"],
            config["moe_layer_freq"], config["attention_bias"], rs["mscale"]) != \
            ("silu", "softmax", "group_limited_greedy", "yarn", 1, False, rs["mscale_all_dim"]):
        raise ValueError("the MoE serving path runs DeepSeek-V2's block: SwiGLU, softmax "
                         "group-limited routing, YaRN rope with mscale = mscale_all_dim, "
                         "an MoE every layer, no bias")
    return dataclasses.replace(
        get_config(config["program_arch"]),
        num_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"], num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        kv_lora_rank=config["kv_lora_rank"], q_lora_rank=config["q_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"], v_head_dim=config["v_head_dim"],
        num_experts=config["published"]["n_routed_experts"],
        experts_held=config["n_routed_experts"],
        first_expert_held=config["first_routed_expert"],
        top_k=config["num_experts_per_tok"], d_ff_expert=config["moe_intermediate_size"],
        num_shared_experts=config["n_shared_experts"],
        first_dense_layers=config["first_k_dense_replace"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rope_theta=float(config["rope_theta"]), rope_factor=float(rs["factor"]),
        rope_original_max_positions=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]), rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        norm_eps=float(config["rms_norm_eps"]), tie_embeddings=config["tie_word_embeddings"],
        dtype=config["torch_dtype"], family="moe", use_mla=True, act="silu", norm="rmsnorm")


@functools.lru_cache(maxsize=None)
def _maker(shape, dtype, kind: str):
    """A jitted maker of one seeded array: matrices normal / sqrt(fan_in),
    the embedding 0.02 N, norm scales 1 + 0.1 N."""
    def make(key):
        z = jax.random.normal(key, shape, jnp.float32)
        if kind == "embed":
            z = 0.02 * z
        elif kind == "scale":
            z = 1.0 + 0.1 * z
        else:
            z = z / np.sqrt(shape[-2])
        return z.astype(dtype)
    return jax.jit(make)


@functools.partial(jax.jit, donate_argnums=0)
def _put(stack, part, i):
    return jax.lax.dynamic_update_index_in_dim(stack, part, i, 0)


def make_weights(abstract, key, stacked=("blocks", "dense_blocks")):
    """Seeded weights for the ``abstract`` tree on the device: leaf ``i``
    from ``fold_in(key, i)``; a leaf under ``stacked`` (layers first) made
    one layer at a time from ``fold_in(fold_in(key, i), layer)``."""
    flat, tree = jax.tree_util.tree_flatten_with_path(abstract)
    out = []
    for i, (where, a) in enumerate(flat):
        name = where[-1].key
        kind = "embed" if name == "embed" else "scale" if name in NORM_SCALES else "matrix"
        k = jax.random.fold_in(key, i)
        if where[0].key in stacked:
            make = _maker(a.shape[1:], a.dtype, kind)
            leaf = jnp.zeros(a.shape, a.dtype)
            for layer in range(a.shape[0]):
                leaf = _put(leaf, make(jax.random.fold_in(k, layer)), layer)
        else:
            leaf = _maker(a.shape, a.dtype, kind)(k)
        out.append(jax.block_until_ready(leaf))
    return jax.tree_util.tree_unflatten(tree, out)


def setup(config: dict, traffic: dict, seed: int) -> State:
    from repro.core import make_paper_cluster
    from repro.models.model import Model
    from repro.serving import ServingEngine

    t = now()
    cfg = model_config(config)
    abstract, _ = Model(cfg).init(abstract=True)
    key = jnp.asarray(generate.key_words(seed, generate.WEIGHTS))
    params = make_weights(abstract, key)
    state = State(config, traffic, params, generate.prompts(traffic, config, seed))
    state.engine = ServingEngine(cfg, params, make_paper_cluster(),
                                 max_batch=traffic["max_batch"])
    state.parts["weights"], t = now() - t, now()
    _call(state.engine, state.prompts[0], traffic["new_tokens"])    # warm-up call
    state.parts["warm-up"] = now() - t
    return state


def serve(state: State, traffic: dict, seconds: float, rec) -> list:
    n_req, p, n = traffic["requests_per_call"], traffic["prompt_len"], traffic["new_tokens"]
    per_call = flops_mla.serve_call_flops(state.config, n_req, p, n)
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
    """Over a sample of the window's requests drawn from the seed, the gap
    by which each served token's reference logit lies below the
    reference's best at its position: ``logit_gap_mean``, their mean, the
    number the cell is held to, and ``logit_gap``, the widest. With
    ``control``, also the same two of the tokens that the reference in
    float8 ranks first. The program's state and then its weights go."""
    state.engine = None
    tr = state.traffic
    n_req, p, n = tr["requests_per_call"], tr["prompt_len"], tr["new_tokens"]
    picks = generate.sample(len(units) * n_req, tr["sample"], seed)
    prompts = np.stack([state.prompts[units[i // n_req]["prompt"]][i % n_req] for i in picks])
    served = np.stack([units[i // n_req]["out"][i % n_req] for i in picks])
    params, state.params = state.params, None
    if served.shape != (len(picks), n) or served.min() < 0 \
            or served.max() >= state.config["vocab_size"]:
        return {"logit_gap": float("inf"), "logit_gap_mean": float("inf")}
    tokens = np.concatenate([prompts, served[:, :-1]], axis=1).astype(np.int32)
    served = served.astype(np.int32)
    with jax.default_matmul_precision("highest"):
        if not control:
            gap = ref.gaps(state.config, params, tokens, served, p)
            return {"logit_gap": float(gap.max()), "logit_gap_mean": float(gap.mean())}
        gap, low = ref.gaps(state.config, params, tokens, served, p, quant="fp8")
    return {"logit_gap": float(gap.max()), "logit_gap_mean": float(gap.mean()),
            "control.logit_gap": float(low.max()), "control.logit_gap_mean": float(low.mean())}
