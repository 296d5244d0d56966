"""The serving path of a Mamba-2 / attention hybrid MoE decoder
(Granite-4.0-H): one chip's share of an expert-parallel deployment behind
``ServingEngine.serve``, greedy.

The configuration file states the published model and the share held here:
``num_local_experts`` experts of each layer from ``first_local_expert``,
of the ``published`` count that the router scores; ``layer_types`` gives
the layers, one period of which is the program's ``block_pattern``.
Weights are made from the seed on the device leaf by leaf, a leaf stacked
over super-blocks one slice at a time (``bench/paths/serving_moe.py``'s
makers); the Mamba-2 vectors have makers of their own, in the ranges
Mamba-2 initialises to, and so has the tied embedding (``_own_maker``).
Each unit of the window is one ``serve`` call of ``requests_per_call``
requests with prompts from the seed, inside a ``bench.serve`` span; set-up
warms up with one whole call of the window's shape. The check compares the served tokens of a sample of the window's
requests with ``bench/ref/granite_hybrid.py``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops_hybrid, generate
from bench.harness import now
from bench.paths.serving import _call
from bench.paths.serving_moe import NORM_SCALES, _maker, _put
from bench.ref import granite_hybrid as ref

# leaves made by ``_own_maker``: the Mamba-2 mixer's vectors and the embedding
OWN = ("a_log", "dt_bias", "d_skip", "norm", "conv_bias", "embed")


@dataclasses.dataclass
class State:
    config: dict
    traffic: dict
    params: object
    prompts: np.ndarray
    engine: object = None
    parts: dict = dataclasses.field(default_factory=dict)


def period(kinds) -> int:
    """The shortest period that repeats to ``kinds``."""
    n = len(kinds)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and list(kinds) == list(kinds[:p]) * (n // p))


def model_config(config: dict):
    """The program's ``ModelConfig`` set to what the config file states."""
    from repro.configs import get_config
    if (config["hidden_act"], config["normalization_function"],
            config["position_embedding_type"], config["mamba_proj_bias"],
            config["attention_bias"], config["rope_scaling"]) != \
            ("silu", "rmsnorm", "nope", False, False, None):
        raise ValueError("the hybrid serving path runs Granite-4.0-H's block: SwiGLU experts, "
                         "RMSNorm, attention with no position embedding and no bias, "
                         "Mamba-2 projections without bias")
    d, heads = config["hidden_size"], config["num_attention_heads"]
    if config["mamba_n_heads"] * config["mamba_d_head"] != config["mamba_expand"] * d:
        raise ValueError("Mamba-2 heads x head width must be mamba_expand x hidden_size")
    shared, rest = divmod(config["shared_intermediate_size"], config["intermediate_size"])
    if rest:
        raise ValueError("the shared MLP must be a whole number of expert widths")
    kinds = tuple(ref.KINDS[t] for t in config["layer_types"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer")
    return dataclasses.replace(
        get_config(config["program_arch"]),
        num_layers=config["num_hidden_layers"], d_model=d, num_heads=heads,
        num_kv_heads=config["num_key_value_heads"], head_dim=d // heads,
        vocab_size=config["vocab_size"], block_pattern=kinds[:period(kinds)], local_window=0,
        rope_theta=0.0, attention_multiplier=float(config["attention_multiplier"]),
        ssm_state=config["mamba_d_state"], ssm_conv=config["mamba_d_conv"],
        ssm_expand=config["mamba_expand"], ssm_head_dim=config["mamba_d_head"],
        ssm_chunk=config["mamba_chunk_size"], ssm_ngroups=config["mamba_n_groups"],
        ssm_conv_bias=config["mamba_conv_bias"],
        num_experts=config["published"]["num_local_experts"],
        experts_held=config["num_local_experts"], first_expert_held=config["first_local_expert"],
        top_k=config["num_experts_per_tok"], d_ff_expert=config["intermediate_size"],
        num_shared_experts=shared, norm_topk_prob=True, n_group=1, topk_group=1,
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        norm_eps=float(config["rms_norm_eps"]), tie_embeddings=config["tie_word_embeddings"],
        dtype=config["torch_dtype"], family="hybrid", act="silu", norm="rmsnorm")


@functools.lru_cache(maxsize=None)
def _own_maker(shape, dtype, name: str):
    """A jitted maker of one seeded leaf of ``OWN``: -A = exp(a_log)
    uniform in [1, 16]; dt_bias the inverse softplus of dt log-uniform in
    [0.001, 0.1]; D and the gated norm's scale 1 + 0.1 N; the conv bias
    0.1 N; the tied embedding 0.002 N. At the 0.02 N of the other paths the
    embedding, times 12 in the residual stream, would make each position's
    own token the tied head's argmax by some 15 standard deviations of the
    other logits, whatever the layers compute."""
    def make(key):
        u = jax.random.uniform(key, shape, jnp.float32)
        z = jax.random.normal(key, shape, jnp.float32)
        if name == "a_log":
            v = jnp.log(1.0 + 15.0 * u)
        elif name == "dt_bias":
            dt = jnp.exp(np.log(1e-3) + u * (np.log(0.1) - np.log(1e-3)))
            v = dt + jnp.log(-jnp.expm1(-dt))
        elif name == "conv_bias":
            v = 0.1 * z
        elif name == "embed":
            v = 0.002 * z
        else:
            v = 1.0 + 0.1 * z
        return v.astype(dtype)
    return jax.jit(make)


def make_weights(abstract, key):
    """Seeded weights for the ``abstract`` tree on the device: leaf ``i``
    from ``fold_in(key, i)``; a leaf under ``super`` (super-blocks first)
    made one super-block at a time from ``fold_in(fold_in(key, i), s)``."""
    flat, tree = jax.tree_util.tree_flatten_with_path(abstract)
    out = []
    for i, (where, a) in enumerate(flat):
        name = where[-1].key
        stacked = where[0].key == "super"
        shape = a.shape[1:] if stacked else a.shape
        if name in OWN:
            make = _own_maker(shape, a.dtype, name)
        else:
            make = _maker(shape, a.dtype, "scale" if name in NORM_SCALES else "matrix")
        k = jax.random.fold_in(key, i)
        if stacked:
            leaf = jnp.zeros(a.shape, a.dtype)
            for s in range(a.shape[0]):
                leaf = _put(leaf, make(jax.random.fold_in(k, s)), s)
        else:
            leaf = make(k)
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
    per_call = flops_hybrid.serve_call_flops(state.config, n_req, p, n)
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
    reference's best at its position: ``logit_gap``, the widest, and
    ``logit_gap_mean``, their mean. With ``control``, also the same two of
    the tokens that the reference in float8 ranks first. The program's
    state and then its weights go."""
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
