"""Unified multi-family model: dense / moe / ssm / hybrid / audio / vlm.

One ``Model`` object per ``ModelConfig`` exposes:

  init(rng, abstract)        -> (params, specs)
  forward(params, batch, mode=)     -> (logits, aux_loss, prefill_kv_or_None)
  loss_fn(params, batch)            -> (loss, nll)
  prefill(params, tokens, cache_len) -> (last_logits, cache)   [where can_prefill]
  decode_step(params, token, cache [, memory_kv built into cache]) -> (logits, cache)
  init_cache(batch, cache_len, abstract) -> (cache, cache_specs)
  input_specs(shape_name)    -> kwargs of ShapeDtypeStructs for the step fns

Layer stacks are scanned over stacked params (HLO stays small at 61–100
layers); hybrid/vlm scan over repeating super-blocks. Remat is applied per
block in training via ``jax.checkpoint``.

A ``hybrid`` super-block is one period of ``block_pattern``: each block is
norm -> mixer (RG-LRU, Mamba-2 or attention, local or global) -> residual
(times ``residual_multiplier``), then norm -> FFN (an MoE where the config
has experts, else the dense MLP) -> residual. Its decode cache holds each
block's state side by side under explicit names (``_STATE``): the conv
window and recurrent state of RG-LRU and Mamba-2 blocks, the k/v of
attention blocks.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import INPUT_SHAPES, ModelConfig
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import ssm as SSM
from repro.utils.params import ParamBuilder, count_params
from repro.utils.sharding import shard
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name

Pytree = Any

# the most that the widest per-token temporary of one block of a prefill
# may take (``Model.prefill_rows``)
PREFILL_BLOCK_BYTES = 1 << 30

# the decode-cache entries of one hybrid block, by kind: ``s{j}_<name>``
# (stacked over super-blocks) or ``t{t}_<name>`` (a tail block)
_STATE = {"rec": ("conv", "h"), "mamba": ("conv", "ssm"), "attn": ("k", "v")}


def _state_names(prefix: str, kind: str) -> Tuple[str, ...]:
    return tuple(f"{prefix}_{n}" for n in _STATE[kind])


def is_recurrent_state(name: str) -> bool:
    """Whether a decode-cache entry is a recurrent state or a conv window,
    whose size does not grow with the context (``conv``, ``ssm``,
    ``s{j}_h``, ...), rather than k/v, latents or counters."""
    return name.rsplit("_", 1)[-1] in ("conv", "h", "ssm")


# ---------------------------------------------------------------------------
# per-family block init
# ---------------------------------------------------------------------------

def _init_dense_block(b: ParamBuilder, cfg: ModelConfig, use_moe: bool):
    L.init_norm(b, "ln1", cfg.d_model, cfg.norm)
    if cfg.use_mla:
        MLA.init_mla(b, "attn", cfg)
    else:
        L.init_attention(b, "attn", cfg)
    L.init_norm(b, "ln2", cfg.d_model, cfg.norm)
    if use_moe:
        MOE.init_moe(b, "ffn", cfg)
    else:
        L.init_mlp(b, "ffn", cfg)


def _init_ssm_block(b: ParamBuilder, cfg: ModelConfig):
    L.init_norm(b, "ln", cfg.d_model, cfg.norm)
    SSM.init_ssm(b, "mixer", cfg)


def _init_hybrid_block(b: ParamBuilder, cfg: ModelConfig, kind: str):
    L.init_norm(b, "ln1", cfg.d_model, cfg.norm)
    if kind == "rec":
        RG.init_rglru(b, "mixer", cfg)
    elif kind == "mamba":
        SSM.init_ssm(b, "mixer", cfg)
    else:
        L.init_attention(b, "attn", cfg)
    L.init_norm(b, "ln2", cfg.d_model, cfg.norm)
    if cfg.num_experts:
        MOE.init_moe(b, "ffn", cfg)
    else:
        L.init_mlp(b, "ffn", cfg)


def _init_cross_block(b: ParamBuilder, cfg: ModelConfig, gated: bool):
    L.init_norm(b, "ln1", cfg.d_model, cfg.norm)
    L.init_cross_attention(b, "xattn", cfg, gated=gated)
    L.init_norm(b, "ln2", cfg.d_model, cfg.norm)
    L.init_mlp(b, "ffn", cfg)


def _stack_init(rng, n: int, fn, abstract: bool, dtype):
    """Build ``n`` identical blocks and stack along a leading layer axis."""
    if abstract:
        b = ParamBuilder(None, dtype=dtype, abstract=True)
        fn(b)
        params, specs = b.build()
        from repro.utils.params import abstract_stack
        return abstract_stack(params, specs, n)
    outs = []
    for i in range(n):
        b = ParamBuilder(jax.random.fold_in(rng, i), dtype=dtype)
        fn(b)
        outs.append(b.build())
    del b   # stack_layers frees each layer's arrays once nothing else holds them
    from repro.utils.params import stack_layers
    return stack_layers(outs)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class Model:
    def __init__(self, cfg: ModelConfig):
        cfg.validate()
        self.cfg = cfg
        # scan unrolling for layer stacks: 1 = rolled loop (fast compiles);
        # True = fully unrolled (dry-run: makes cost_analysis count every
        # layer, since XLA reports while-loop bodies only once)
        self.scan_unroll = 1
        # remat policy: "full" recomputes everything in bwd (min memory, but
        # re-runs the fwd all-reduces); "outputs" saves the post-all-reduce
        # attn/ffn outputs (checkpoint_name) — ~1/3 less collective traffic
        # for one extra bf16 activation pair per layer.
        self.remat_policy = "full"
        # MoE execution: "auto" = expert-parallel over model axis;
        # "2d" = weight-resident 2D expert parallelism (decode regime)
        self.moe_impl = "auto"
        # dense block execution: "gspmd" (sharding constraints) or
        # "shardmap" (explicit Megatron-SP collectives; train path)
        self.block_impl = "gspmd"

    def _scan(self, f, init, xs):
        return jax.lax.scan(f, init, xs, unroll=self.scan_unroll)

    # -- structure helpers --------------------------------------------------

    @property
    def _pattern(self) -> Tuple[str, ...]:
        return self.cfg.block_pattern or ()

    @property
    def _n_super(self) -> int:
        cfg = self.cfg
        if cfg.family == "hybrid":
            return cfg.num_layers // len(self._pattern)
        if cfg.family == "vlm":
            return cfg.num_layers // cfg.cross_attn_every
        return 0

    @property
    def _n_tail(self) -> int:
        if self.cfg.family == "hybrid":
            return self.cfg.num_layers % len(self._pattern)
        return 0

    @property
    def _n_scanned(self) -> int:
        cfg = self.cfg
        if cfg.family in ("dense",):
            return cfg.num_layers
        if cfg.family == "moe":
            return cfg.num_layers - cfg.first_dense_layers
        if cfg.family == "ssm":
            return cfg.num_layers
        if cfg.family == "audio":
            return cfg.num_layers
        return 0

    # -- init ---------------------------------------------------------------

    def init(self, rng: Optional[jax.Array] = None, abstract: bool = False):
        cfg = self.cfg
        dtype = cfg.jnp_dtype
        if rng is None:
            rng = jax.random.PRNGKey(0)
        b = ParamBuilder(rng if not abstract else None, dtype=dtype, abstract=abstract)
        L.init_embed(b, cfg)
        L.init_norm(b, "final_norm", cfg.d_model, cfg.norm)
        params, specs = b.build()
        r = jax.random.fold_in(rng, 999)

        if cfg.family in ("dense", "moe"):
            fd = cfg.first_dense_layers if cfg.family == "moe" else 0
            if fd:
                params["dense_blocks"], specs["dense_blocks"] = _stack_init(
                    jax.random.fold_in(r, 1), fd,
                    lambda bb: _init_dense_block(bb, cfg, use_moe=False), abstract, dtype)
            params["blocks"], specs["blocks"] = _stack_init(
                jax.random.fold_in(r, 2), cfg.num_layers - fd,
                lambda bb: _init_dense_block(bb, cfg, use_moe=(cfg.family == "moe")),
                abstract, dtype)
        elif cfg.family == "ssm":
            params["blocks"], specs["blocks"] = _stack_init(
                r, cfg.num_layers, lambda bb: _init_ssm_block(bb, cfg), abstract, dtype)
        elif cfg.family == "hybrid":
            def init_super(bb: ParamBuilder):
                for j, kind in enumerate(self._pattern):
                    _init_hybrid_block(bb.sub(f"b{j}_{kind}"), cfg, kind)
            params["super"], specs["super"] = _stack_init(
                jax.random.fold_in(r, 1), self._n_super, init_super, abstract, dtype)
            for t in range(self._n_tail):
                kind = self._pattern[t % len(self._pattern)]
                tb = ParamBuilder(jax.random.fold_in(r, 100 + t) if not abstract else None,
                                  dtype=dtype, abstract=abstract)
                _init_hybrid_block(tb, cfg, kind)
                params[f"tail{t}"], specs[f"tail{t}"] = tb.build()
        elif cfg.family == "audio":
            params["enc_blocks"], specs["enc_blocks"] = _stack_init(
                jax.random.fold_in(r, 1), cfg.encoder_layers,
                lambda bb: (L.init_norm(bb, "ln1", cfg.d_model, cfg.norm),
                            L.init_attention(bb, "attn", cfg),
                            L.init_norm(bb, "ln2", cfg.d_model, cfg.norm),
                            L.init_mlp(bb, "ffn", cfg)), abstract, dtype)
            eb = ParamBuilder(jax.random.fold_in(r, 2) if not abstract else None,
                              dtype=dtype, abstract=abstract)
            L.init_norm(eb, "enc_final_norm", cfg.d_model, cfg.norm)
            eb.param("dec_pos", (cfg.max_positions, cfg.d_model), (None, None),
                     init="embedding")
            p2, s2 = eb.build()
            params.update(p2)
            specs.update(s2)

            def init_dec(bb: ParamBuilder):
                L.init_norm(bb, "ln1", cfg.d_model, cfg.norm)
                L.init_attention(bb, "attn", cfg)
                L.init_norm(bb, "lnx", cfg.d_model, cfg.norm)
                L.init_cross_attention(bb, "xattn", cfg, gated=False)
                L.init_norm(bb, "ln2", cfg.d_model, cfg.norm)
                L.init_mlp(bb, "ffn", cfg)
            params["blocks"], specs["blocks"] = _stack_init(
                jax.random.fold_in(r, 3), cfg.num_layers, init_dec, abstract, dtype)
        elif cfg.family == "vlm":
            n_self = cfg.cross_attn_every - 1

            def init_super(bb: ParamBuilder):
                for j in range(n_self):
                    sb = bb.sub(f"self{j}")
                    _init_dense_block(sb, cfg, use_moe=False)
                _init_cross_block(bb.sub("cross"), cfg, gated=True)
            params["super"], specs["super"] = _stack_init(
                r, self._n_super, init_super, abstract, dtype)
        else:
            raise ValueError(cfg.family)
        return params, specs

    # -- block applications (full sequence) ---------------------------------

    def _dense_block(self, p, x, positions, *, window, use_moe, collect_kv=False):
        """One decoder block. Returns (x, aux, kv or None, MoE counts or
        None); with ``collect_kv`` (a prefill) the experts drop nothing."""
        cfg = self.cfg
        h = L.apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
        if cfg.use_mla:
            attn_out, kv = MLA.apply_mla(p["attn"], h, cfg, positions)
        else:
            attn_out, kv = L.apply_attention(
                p["attn"], h, cfg, positions, causal=True, window=window)
        # under sequence-parallel rules this requests a reduce-scatter at the
        # out-projection instead of all-reduce + re-shard (no-op otherwise)
        attn_out = shard(attn_out, "batch", "seq", None)
        x = x + _checkpoint_name(attn_out, "blk_out")
        h = L.apply_norm(p["ln2"], x, cfg.norm, cfg.norm_eps)
        ffn_out, aux, counts = self._ffn(p["ffn"], h, use_moe=use_moe, serving=collect_kv)
        ffn_out = shard(ffn_out, "batch", "seq", None)
        x = x + _checkpoint_name(ffn_out, "blk_out")
        x = shard(x, "batch", "seq", None)
        return x, aux, (kv if collect_kv else None), counts

    def _ffn(self, p, h, *, use_moe: bool, serving: bool):
        """A block's FFN: the MoE where ``use_moe`` (serving drops nothing
        and gives its counts; training clips at capacity and gives the aux
        loss), else the dense MLP. Returns (out, aux (1,) or per token,
        counts (3,) or None)."""
        cfg = self.cfg
        if not use_moe:
            return L.apply_mlp(p, h, cfg), jnp.zeros((1,), jnp.float32), None
        if serving:
            out, counts = MOE.serve_moe(p, h, cfg, impl=self.moe_impl)
            return out, jnp.zeros((1,), jnp.float32), counts
        out, aux = MOE.apply_moe(p, h, cfg, impl=self.moe_impl)
        return out, aux, None

    def _branch(self, out):
        """A residual branch's output times ``residual_multiplier``."""
        m = self.cfg.residual_multiplier
        return out if m == 1.0 else out * jnp.asarray(m, out.dtype)

    def _hybrid_block(self, p, kind: str, x, positions, *, serving: bool):
        """One block of a hybrid period over the full sequence. Returns (x,
        aux, the block's decode state in ``_STATE`` order, MoE counts or
        None); ``serving`` (a prefill) runs the experts dropless."""
        cfg = self.cfg
        h = L.apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
        if kind == "rec":
            out, st = RG.apply_rglru(p["mixer"], h, cfg)
            state = (st["conv"], st["h"])
        elif kind == "mamba":
            out, st = SSM.apply_ssm(p["mixer"], h, cfg)
            state = (st["conv"], st["ssm"])
        else:
            out, kv = L.apply_attention(p["attn"], h, cfg, positions, causal=True,
                                        window=cfg.local_window)
            state = self._clip_window_kv(kv, x.shape[1]) if cfg.local_window else kv
        x = x + self._branch(out)
        h = L.apply_norm(p["ln2"], x, cfg.norm, cfg.norm_eps)
        f, aux, counts = self._ffn(p["ffn"], h, use_moe=bool(cfg.num_experts),
                                   serving=serving)
        return x + self._branch(f), aux, state, counts

    def _hybrid_block_decode(self, p, kind: str, x, state, pos):
        """One block of a hybrid period for one token against its cache
        entries ``state`` (``_STATE`` order). Returns (x, the new entries,
        MoE counts or None)."""
        cfg = self.cfg
        h = L.apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
        if kind == "rec":
            out, st = RG.apply_rglru_decode(p["mixer"], h, cfg,
                                            {"conv": state[0], "h": state[1]})
            state = (st["conv"], st["h"])
        elif kind == "mamba":
            out, st = SSM.apply_ssm_decode(p["mixer"], h, cfg,
                                           {"conv": state[0], "ssm": state[1]})
            state = (st["conv"], st["ssm"])
        else:
            out, nk, nv = L.apply_attention_decode(p["attn"], h, cfg, state[0], state[1],
                                                   pos, window=cfg.local_window)
            state = (nk, nv)
        x = x + self._branch(out)
        h = L.apply_norm(p["ln2"], x, cfg.norm, cfg.norm_eps)
        f, _, counts = self._ffn(p["ffn"], h, use_moe=bool(cfg.num_experts), serving=True)
        return x + self._branch(f), state, counts

    # -- training / prefill forward -----------------------------------------

    def forward(self, params, batch: Dict[str, jax.Array], *, mode: str = "train",
                window: int = 0, remat: bool = False):
        """Full-sequence forward.

        batch: {"tokens": (B, S) int32 [, "frames": (B, F, D), "images": (B, I, D)]}
        Returns (logits (B, S, V), aux_loss scalar, cache_or_None).
        mode: "train" (logits over all positions) or "prefill" (the last
        position's logits, and the decode state of the S positions under
        ``init_cache``'s names where the model ``can_prefill``: k/v or
        latents of S positions, recurrent states and conv windows; MoE
        layers drop nothing and give their counts as ``moe_counts`` (n, 3)).
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = L.embed_tokens(params, tokens, cfg)
        positions = jnp.arange(S)
        aux_total = jnp.zeros((), jnp.float32)
        collect = mode == "prefill"
        caches: Dict[str, Any] = {}

        def maybe_remat(f):
            if not remat:
                return f
            if self.remat_policy == "outputs":
                pol = jax.checkpoint_policies.save_only_these_names("blk_out")
                return jax.checkpoint(f, policy=pol)
            return jax.checkpoint(f)

        if cfg.family in ("dense", "moe"):
            fd = cfg.first_dense_layers if cfg.family == "moe" else 0

            def mk_body(use_moe):
                def body(carry, p):
                    x, aux = carry
                    if (self.block_impl == "shardmap" and not use_moe
                            and not cfg.use_mla and not collect):
                        from repro.models import smblock as SMB
                        from repro.utils.sharding import current_rules
                        rules = current_rules()
                        assert rules is not None, "shardmap blocks need a mesh"
                        msize = rules.mesh.shape.get("model", 1)
                        if (x.shape[1] % msize == 0
                                and cfg.num_heads % msize == 0):
                            x = SMB.dense_block_shardmap(
                                p, x, cfg, rules.mesh, window=window)
                            return (x, aux), None
                    x, a, kv, counts = self._dense_block(
                        p, x, positions, window=window, use_moe=use_moe,
                        collect_kv=collect)
                    return (x, aux + a.mean()), (kv if counts is None else (kv, counts))
                return body

            kv_d = ()
            if fd:
                (x, aux_total), kv_d = self._scan(
                    maybe_remat(mk_body(False)), (x, aux_total), params["dense_blocks"])
            (x, aux_total), kv_m = self._scan(
                maybe_remat(mk_body(cfg.family == "moe")), (x, aux_total), params["blocks"])
            if collect:
                counts = None
                if cfg.family == "moe":
                    kv_m, counts = kv_m
                names = ("ckv", "krope") if cfg.use_mla else ("k", "v")
                caches.update(zip(names, kv_m))
                caches.update(zip(("d_" + n for n in names), kv_d))
                if counts is not None:
                    caches["moe_counts"] = counts

        elif cfg.family == "ssm":
            def body(carry, p):
                x = carry
                h = L.apply_norm(p["ln"], x, cfg.norm, cfg.norm_eps)
                out, st = SSM.apply_ssm(p["mixer"], h, cfg)
                return x + out, st if collect else None
            x, states = self._scan(maybe_remat(body), x, params["blocks"])
            if collect:
                caches.update(states)

        elif cfg.family == "hybrid":
            counts = []

            def super_body(carry, p):
                x, aux = carry
                states, cs = {}, []
                for j, kind in enumerate(self._pattern):
                    x, a, st, c = self._hybrid_block(p[f"b{j}_{kind}"], kind, x, positions,
                                                     serving=collect)
                    aux = aux + a.mean()
                    states.update(zip(_state_names(f"s{j}", kind), st))
                    if c is not None:
                        cs.append(c)
                x = shard(x, "batch", "seq", None)
                return (x, aux), ((states, jnp.stack(cs) if cs else None) if collect else None)
            (x, aux_total), sup = self._scan(
                maybe_remat(super_body), (x, aux_total), params["super"])
            if collect:
                caches.update(sup[0])
                if sup[1] is not None:
                    counts.append(sup[1].reshape(-1, 3))
            for t in range(self._n_tail):
                kind = self._pattern[t % len(self._pattern)]
                x, a, st, c = self._hybrid_block(params[f"tail{t}"], kind, x, positions,
                                                 serving=collect)
                aux_total = aux_total + a.mean()
                caches.update(zip(_state_names(f"t{t}", kind), st))
                if c is not None:
                    counts.append(c[None])
            if collect and counts:
                caches["moe_counts"] = jnp.concatenate(counts)

        elif cfg.family == "audio":
            memory = self._encode(params, batch["frames"])
            caches_xkv = []

            def body(carry, p):
                x = carry
                h = L.apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
                out, kv = L.apply_attention(p["attn"], h, cfg, positions, causal=True)
                x = x + out
                h = L.apply_norm(p["lnx"], x, cfg.norm, cfg.norm_eps)
                xk, xv = L.cross_kv(p["xattn"], memory, cfg)
                x = x + L.apply_cross_attention(p["xattn"], h, xk, xv, cfg)
                h = L.apply_norm(p["ln2"], x, cfg.norm, cfg.norm_eps)
                x = x + L.apply_mlp(p["ffn"], h, cfg)
                x = shard(x, "batch", "seq", None)
                return x, ((kv, (xk, xv)) if collect else None)
            # learned decoder positions
            x = x + params["dec_pos"].astype(x.dtype)[:S][None]
            x, dec_states = self._scan(maybe_remat(body), x, params["blocks"])
            if collect:
                caches["dec"] = dec_states

        elif cfg.family == "vlm":
            images = batch["images"]
            n_self = cfg.cross_attn_every - 1

            def super_body(carry, p):
                x, aux = carry
                kvs = {}
                for j in range(n_self):
                    x, a, kv, _ = self._dense_block(
                        p[f"self{j}"], x, positions, window=window,
                        use_moe=False, collect_kv=collect)
                    aux = aux + a.mean()
                    if collect:
                        kvs[f"self{j}"] = kv
                cp = p["cross"]
                h = L.apply_norm(cp["ln1"], x, cfg.norm, cfg.norm_eps)
                xk, xv = L.cross_kv(cp["xattn"], images, cfg)
                x = x + L.apply_cross_attention(cp["xattn"], h, xk, xv, cfg)
                h = L.apply_norm(cp["ln2"], x, cfg.norm, cfg.norm_eps)
                x = x + L.apply_mlp(cp["ffn"], h, cfg)
                x = shard(x, "batch", "seq", None)
                if collect:
                    kvs["cross"] = (xk, xv)
                return (x, aux), (kvs if collect else None)
            (x, aux_total), sup = self._scan(
                maybe_remat(super_body), (x, aux_total), params["super"])
            if collect:
                caches["super"] = sup
        else:
            raise ValueError(cfg.family)

        x = L.apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        if mode == "prefill":
            logits = L.unembed(params, x[:, -1:, :], cfg)
            return logits[:, 0, :], aux_total, caches
        logits = L.unembed(params, x, cfg)
        return logits, aux_total, None

    def _clip_window_kv(self, kv, S):
        """The prefill's K/V laid out as the local cache's ring buffer of
        ``local_window`` slots: position p in slot p % window."""
        w = self.cfg.local_window
        k, v = kv
        if S < w:
            pad = w - S
            k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        elif S > w:
            k = jnp.roll(k[:, :, -w:, :], S % w, axis=2)
            v = jnp.roll(v[:, :, -w:, :], S % w, axis=2)
        return (k, v)

    def _encode(self, params, frames: jax.Array) -> jax.Array:
        """Whisper encoder over precomputed frame embeddings (B, F, D)."""
        cfg = self.cfg
        B, F, D = frames.shape
        pos = jnp.arange(F)
        x = frames.astype(cfg.jnp_dtype) + _sinusoid(F, D).astype(cfg.jnp_dtype)
        x = shard(x, "batch", "seq", None)
        positions = jnp.arange(F)

        def body(x, p):
            h = L.apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
            out, _ = L.apply_attention(p["attn"], h, cfg, positions, causal=False)
            x = x + out
            h = L.apply_norm(p["ln2"], x, cfg.norm, cfg.norm_eps)
            x = x + L.apply_mlp(p["ffn"], h, cfg)
            return shard(x, "batch", "seq", None), None
        x, _ = self._scan(body, x, params["enc_blocks"])
        return L.apply_norm(params["enc_final_norm"], x, cfg.norm, cfg.norm_eps)

    # -- loss ---------------------------------------------------------------

    def loss_fn(self, params, batch, *, remat: bool = True):
        cfg = self.cfg
        tokens = batch["tokens"]
        inputs = dict(batch)
        inputs["tokens"] = tokens[:, :-1]
        labels = tokens[:, 1:]
        logits, aux, _ = self.forward(params, inputs, mode="train", remat=remat)
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        nll = (logz - gold).mean()
        return nll + cfg.router_aux_weight * aux, nll

    # -- decode -------------------------------------------------------------

    def init_cache(self, batch: int, cache_len: int, *, abstract: bool = False,
                   memory_len: int = 0):
        """Build an empty decode cache (+ its logical-axes spec tree)."""
        cfg = self.cfg
        dt = cfg.jnp_dtype

        def arr(shape, axes, dtype=dt):
            if abstract:
                a = jax.ShapeDtypeStruct(shape, dtype)
            else:
                a = jnp.zeros(shape, dtype)
            return a, axes

        hd = cfg.head_dim_ if cfg.num_heads else 0
        entries: Dict[str, Any] = {}
        specs: Dict[str, Any] = {}

        def put(name, shape, axes, dtype=dt):
            entries[name], specs[name] = arr(shape, axes, dtype)

        if cfg.family in ("dense", "moe"):
            fd = cfg.first_dense_layers if cfg.family == "moe" else 0
            n = cfg.num_layers - fd
            if cfg.use_mla:
                put("ckv", (n, batch, cache_len, cfg.kv_lora_rank),
                    ("layers", "batch", "kv_seq", None))
                put("krope", (n, batch, cache_len, cfg.qk_rope_head_dim),
                    ("layers", "batch", "kv_seq", None))
                if fd:
                    put("d_ckv", (fd, batch, cache_len, cfg.kv_lora_rank),
                        ("layers", "batch", "kv_seq", None))
                    put("d_krope", (fd, batch, cache_len, cfg.qk_rope_head_dim),
                        ("layers", "batch", "kv_seq", None))
            else:
                kvs = ("layers", "batch", "kv_heads", "kv_seq", None)
                q8 = cfg.kv_cache_dtype == "int8"
                kvdt = jnp.int8 if q8 else dt
                put("k", (n, batch, cfg.num_kv_heads, cache_len, hd), kvs, kvdt)
                put("v", (n, batch, cfg.num_kv_heads, cache_len, hd), kvs, kvdt)
                if q8:
                    scs = ("layers", "batch", "kv_heads", "kv_seq")
                    put("k_scale", (n, batch, cfg.num_kv_heads, cache_len), scs,
                        jnp.float32)
                    put("v_scale", (n, batch, cfg.num_kv_heads, cache_len), scs,
                        jnp.float32)
                if fd:
                    put("d_k", (fd, batch, cfg.num_kv_heads, cache_len, hd), kvs, kvdt)
                    put("d_v", (fd, batch, cfg.num_kv_heads, cache_len, hd), kvs, kvdt)
                    if q8:
                        scs = ("layers", "batch", "kv_heads", "kv_seq")
                        put("d_k_scale", (fd, batch, cfg.num_kv_heads, cache_len),
                            scs, jnp.float32)
                        put("d_v_scale", (fd, batch, cfg.num_kv_heads, cache_len),
                            scs, jnp.float32)
            if cfg.family == "moe":
                # (assignments computed here, most on one expert, dropped)
                put("moe_counts", (3,), (None,), jnp.int32)
        elif cfg.family == "ssm":
            di, H, G, d_bc = SSM.ssm_dims(cfg)
            nconv = di + 2 * d_bc
            put("conv", (cfg.num_layers, batch, cfg.ssm_conv - 1, nconv),
                ("layers", "batch", None, "ff"))
            put("ssm", (cfg.num_layers, batch, H, cfg.ssm_head_dim, cfg.ssm_state),
                ("layers", "batch", "heads", None, None), jnp.float32)
        elif cfg.family == "hybrid":
            W = RG.lru_width(cfg)
            di, H, _, d_bc = SSM.ssm_dims(cfg)
            win = cfg.local_window or cache_len
            # per kind: the shape, logical axes and dtype of each entry
            # (``_STATE`` order) of one block, without the layer axis
            kv = ((batch, cfg.num_kv_heads, win, hd), ("batch", "kv_heads", "kv_seq", None), dt)
            entries_of = {
                "rec": (((batch, RG._CONV_K - 1, W), ("batch", None, "ff"), dt),
                        ((batch, W), ("batch", "ff"), jnp.float32)),
                "mamba": (((batch, cfg.ssm_conv - 1, di + 2 * d_bc), ("batch", None, "ff"), dt),
                          ((batch, H, cfg.ssm_head_dim, cfg.ssm_state),
                           ("batch", "heads", None, None), jnp.float32)),
                "attn": (kv, kv),
            }
            for j, kind in enumerate(self._pattern):
                for name, (shape, axes, dtype) in zip(_state_names(f"s{j}", kind),
                                                      entries_of[kind]):
                    put(name, (self._n_super,) + shape, ("layers",) + axes, dtype)
            for t in range(self._n_tail):
                kind = self._pattern[t % len(self._pattern)]
                for name, entry in zip(_state_names(f"t{t}", kind), entries_of[kind]):
                    put(name, *entry)
            if cfg.num_experts:
                put("moe_counts", (3,), (None,), jnp.int32)
        elif cfg.family == "audio":
            kvs = ("layers", "batch", "kv_heads", "kv_seq", None)
            n = cfg.num_layers
            put("k", (n, batch, cfg.num_kv_heads, cache_len, hd), kvs)
            put("v", (n, batch, cfg.num_kv_heads, cache_len, hd), kvs)
            m = memory_len or cfg.num_frames
            xs = ("layers", "batch", "kv_heads", None, None)
            put("xk", (n, batch, cfg.num_kv_heads, m, hd), xs)
            put("xv", (n, batch, cfg.num_kv_heads, m, hd), xs)
        elif cfg.family == "vlm":
            n_self = cfg.cross_attn_every - 1
            ns = self._n_super
            kvs = ("layers", None, "batch", "kv_heads", "kv_seq", None)
            put("k", (ns, n_self, batch, cfg.num_kv_heads, cache_len, hd), kvs)
            put("v", (ns, n_self, batch, cfg.num_kv_heads, cache_len, hd), kvs)
            m = memory_len or cfg.num_image_tokens
            xs = ("layers", "batch", "kv_heads", None, None)
            put("xk", (ns, batch, cfg.num_kv_heads, m, hd), xs)
            put("xv", (ns, batch, cfg.num_kv_heads, m, hd), xs)
        else:
            raise ValueError(cfg.family)

        put("pos", (), (), jnp.int32)
        return entries, specs

    @property
    def can_prefill(self) -> bool:
        """Whether ``prefill`` fills the cache that stepping ``decode_step``
        through the prompt would: a dense or MoE decoder whose cache is a
        plain k/v or MLA latent cache, a Mamba-2 stack, or a hybrid of
        Mamba-2 and attention blocks (the chunked scan's final state and the
        last conv inputs are what the steps carry). MoE layers drop no token
        on either path, so the full-sequence pass routes each token as a
        step does. Elsewhere the cache has another layout (int8) or holds
        RG-LRU or cross state."""
        cfg = self.cfg
        if cfg.family == "hybrid":
            return "rec" not in self._pattern
        return cfg.family == "ssm" or (cfg.family in ("dense", "moe")
                                       and cfg.kv_cache_dtype != "int8")

    def prefill_rows(self, batch: int, prompt_len: int) -> int:
        """Sequences per block of the prefill: the most, dividing ``batch``,
        whose widest per-token temporary takes at most
        ``PREFILL_BLOCK_BYTES``: the per-head q, k and v (MLA expands its
        latent to 128 heads of 192 + 192 + 128), the Mamba-2
        in-projection, or the expert dispatch operand of top_k rows of
        ``d_model`` a token."""
        cfg = self.cfg
        if cfg.use_mla:
            width = cfg.num_heads * (2 * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
                                     + cfg.v_head_dim)
        else:
            width = (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim_
        if cfg.ssm_state:
            di, H, _, d_bc = SSM.ssm_dims(cfg)
            width = max(width, 2 * di + 2 * d_bc + H)
        if cfg.num_experts:
            width = max(width, cfg.top_k * cfg.d_model)
        per_row = prompt_len * width * cfg.jnp_dtype.itemsize
        rows = batch
        while rows > 1 and (batch % rows or rows * per_row > PREFILL_BLOCK_BYTES):
            rows -= 1
        return rows

    def prefill(self, params, tokens: jax.Array, cache_len: int):
        """The whole prompt in full-sequence passes. tokens: (B, P) int32.

        Returns (logits of the last position (B, V), the decode cache of
        ``cache_len`` slots that ``init_cache`` describes, with the prompt's
        k/v or latents in slots [0, P), zeros after them, recurrent states
        and conv windows after P positions, ``pos`` = P and, for MoE, the
        prompt's counts). Blocks of ``prefill_rows`` sequences go through
        one after another inside the call, each writing its rows of the
        cache. Only where ``can_prefill``."""
        if not self.can_prefill:
            raise ValueError(f"{self.cfg.name}: prefill needs a decoder whose cache is "
                             "plain k/v, latents or Mamba-2 state")
        B, P = tokens.shape
        if cache_len < P:
            raise ValueError(f"cache of {cache_len} slots for a prompt of {P}")
        rows = self.prefill_rows(B, P)
        if rows == B:
            return self._prefill_block(params, tokens, cache_len)

        cache, axes = self.init_cache(B, cache_len)

        def block(i, carry):
            logits, cache = carry
            lg, part = self._prefill_block(
                params, jax.lax.dynamic_slice_in_dim(tokens, i * rows, rows), cache_len)
            logits = jax.lax.dynamic_update_slice_in_dim(logits, lg, i * rows, 0)
            cache = {name: (MOE.add_counts(a, part[name][None]) if name == "moe_counts"
                            else a if name == "pos"
                            else jax.lax.dynamic_update_slice_in_dim(
                                a, part[name], i * rows, axes[name].index("batch")))
                     for name, a in cache.items()}
            return logits, cache

        cache["pos"] = jnp.asarray(P, jnp.int32)
        logits = jnp.zeros((B, self.cfg.padded_vocab), self.cfg.jnp_dtype)
        return jax.lax.fori_loop(0, B // rows, block, (logits, cache))

    def _prefill_block(self, params, tokens: jax.Array, cache_len: int):
        """``prefill`` of one block of sequences in one pass: the forward's
        states in the cache's types, each k/v or latent padded with zeros
        from P to ``cache_len`` slots."""
        B, P = tokens.shape
        logits, _, states = self.forward(params, {"tokens": tokens}, mode="prefill")
        want, _ = self.init_cache(B, cache_len, abstract=True)
        cache = {}
        for name, a in states.items():
            if name == "moe_counts":
                cache[name] = MOE.add_counts(jnp.zeros((3,), jnp.int32), a)
            else:
                w = want[name]
                cache[name] = jnp.pad(a.astype(w.dtype),
                                      [(0, n - m) for m, n in zip(a.shape, w.shape)])
        cache["pos"] = jnp.asarray(P, jnp.int32)
        return logits, cache

    def decode_step(self, params, token: jax.Array, cache: Dict[str, Any],
                    *, window: int = 0):
        """token: (B,) int32. Returns (logits (B, V), new_cache)."""
        cfg = self.cfg
        B = token.shape[0]
        x = L.embed_tokens(params, token[:, None], cfg)
        pos = cache["pos"]
        new_cache = dict(cache)
        new_cache["pos"] = pos + 1

        if cfg.family in ("dense", "moe"):
            fd = cfg.first_dense_layers if cfg.family == "moe" else 0

            q8 = (not cfg.use_mla) and cfg.kv_cache_dtype == "int8"

            def mk_body(use_moe):
                def body(x, sl):
                    p, c = sl
                    h = L.apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
                    if cfg.use_mla:
                        out, nckv, nkr = MLA.apply_mla_decode(
                            p["attn"], h, cfg, c[0], c[1], pos)
                        nc = (nckv, nkr)
                    elif q8:
                        out, nk, nv, (nks, nvs) = L.apply_attention_decode(
                            p["attn"], h, cfg, c[0], c[1], pos, window=window,
                            cache_scales=(c[2], c[3]))
                        nc = (nk, nv, nks, nvs)
                    else:
                        out, nk, nv = L.apply_attention_decode(
                            p["attn"], h, cfg, c[0], c[1], pos, window=window)
                        nc = (nk, nv)
                    x = x + out
                    h = L.apply_norm(p["ln2"], x, cfg.norm, cfg.norm_eps)
                    if use_moe:
                        f, counts = MOE.serve_moe(p["ffn"], h, cfg, impl=self.moe_impl)
                        return x + f, (nc, counts)
                    return x + L.apply_mlp(p["ffn"], h, cfg), nc
                return body

            if cfg.use_mla:
                kv_names = ("ckv", "krope")
            elif q8:
                kv_names = ("k", "v", "k_scale", "v_scale")
            else:
                kv_names = ("k", "v")
            if fd:
                d_names = tuple("d_" + n for n in kv_names)
                x, outs = self._scan(
                    mk_body(False), x,
                    (params["dense_blocks"], tuple(cache[n] for n in d_names)))
                for nm, arr in zip(d_names, outs):
                    new_cache[nm] = arr
            x, outs = self._scan(
                mk_body(cfg.family == "moe"), x,
                (params["blocks"], tuple(cache[n] for n in kv_names)))
            if cfg.family == "moe":
                outs, counts = outs
                new_cache["moe_counts"] = MOE.add_counts(cache["moe_counts"], counts)
            for nm, arr in zip(kv_names, outs):
                new_cache[nm] = arr

        elif cfg.family == "ssm":
            def body(x, sl):
                p, conv, ssm_st = sl
                h = L.apply_norm(p["ln"], x, cfg.norm, cfg.norm_eps)
                out, st = SSM.apply_ssm_decode(p["mixer"], h, cfg,
                                               {"conv": conv, "ssm": ssm_st})
                return x + out, (st["conv"], st["ssm"])
            x, (nconv, nssm) = self._scan(
                body, x, (params["blocks"], cache["conv"], cache["ssm"]))
            new_cache["conv"], new_cache["ssm"] = nconv, nssm

        elif cfg.family == "hybrid":
            blocks = [(j, kind, _state_names(f"s{j}", kind))
                      for j, kind in enumerate(self._pattern)]

            def super_body(x, sl):
                p, c = sl
                outs, cs = {}, []
                for j, kind, names in blocks:
                    x, st, cnt = self._hybrid_block_decode(
                        p[f"b{j}_{kind}"], kind, x, tuple(c[n] for n in names), pos)
                    outs.update(zip(names, st))
                    if cnt is not None:
                        cs.append(cnt)
                return x, (outs, jnp.stack(cs) if cs else None)
            sup_cache = {n: cache[n] for _, _, names in blocks for n in names}
            x, (new_sup, counts) = self._scan(super_body, x, (params["super"], sup_cache))
            new_cache.update(new_sup)
            counts = [] if counts is None else [counts.reshape(-1, 3)]
            for t in range(self._n_tail):
                kind = self._pattern[t % len(self._pattern)]
                names = _state_names(f"t{t}", kind)
                x, st, cnt = self._hybrid_block_decode(
                    params[f"tail{t}"], kind, x, tuple(cache[n] for n in names), pos)
                new_cache.update(zip(names, st))
                if cnt is not None:
                    counts.append(cnt[None])
            if counts:
                new_cache["moe_counts"] = MOE.add_counts(cache["moe_counts"],
                                                         jnp.concatenate(counts))

        elif cfg.family == "audio":
            x = x + params["dec_pos"].astype(x.dtype)[pos][None, None, :]

            def body(x, sl):
                p, k, v, xk, xv = sl
                h = L.apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
                out, nk, nv = L.apply_attention_decode(p["attn"], h, cfg, k, v, pos)
                x = x + out
                h = L.apply_norm(p["lnx"], x, cfg.norm, cfg.norm_eps)
                x = x + L.apply_cross_attention(p["xattn"], h, xk, xv, cfg)
                h = L.apply_norm(p["ln2"], x, cfg.norm, cfg.norm_eps)
                x = x + L.apply_mlp(p["ffn"], h, cfg)
                return x, (nk, nv)
            x, (nk, nv) = self._scan(
                body, x, (params["blocks"], cache["k"], cache["v"],
                          cache["xk"], cache["xv"]))
            new_cache["k"], new_cache["v"] = nk, nv

        elif cfg.family == "vlm":
            n_self = cfg.cross_attn_every - 1

            def super_body(x, sl):
                p, k, v, xk, xv = sl
                nks, nvs = [], []
                for j in range(n_self):
                    bp = p[f"self{j}"]
                    h = L.apply_norm(bp["ln1"], x, cfg.norm, cfg.norm_eps)
                    out, nk, nv = L.apply_attention_decode(
                        bp["attn"], h, cfg, k[j], v[j], pos, window=window)
                    nks.append(nk)
                    nvs.append(nv)
                    x = x + out
                    h = L.apply_norm(bp["ln2"], x, cfg.norm, cfg.norm_eps)
                    x = x + L.apply_mlp(bp["ffn"], h, cfg)
                cp = p["cross"]
                h = L.apply_norm(cp["ln1"], x, cfg.norm, cfg.norm_eps)
                x = x + L.apply_cross_attention(cp["xattn"], h, xk, xv, cfg)
                h = L.apply_norm(cp["ln2"], x, cfg.norm, cfg.norm_eps)
                x = x + L.apply_mlp(cp["ffn"], h, cfg)
                return x, (jnp.stack(nks), jnp.stack(nvs))
            x, (nk, nv) = self._scan(
                super_body, x,
                (params["super"], cache["k"], cache["v"], cache["xk"], cache["xv"]))
            new_cache["k"], new_cache["v"] = nk, nv
        else:
            raise ValueError(cfg.family)

        x = L.apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        logits = L.unembed(params, x, cfg)
        return logits[:, 0, :], new_cache

    def fill_cross_cache(self, params, cache, memory: jax.Array):
        """Precompute cross-attention K/V from modality memory into ``cache``.

        audio: ``memory`` = frame embeddings (B, F, D) -> runs the encoder.
        vlm:   ``memory`` = patch embeddings (B, I, D).
        """
        cfg = self.cfg
        if cfg.family == "audio":
            mem = self._encode(params, memory)
            xk, xv = [], []
            for i in range(cfg.num_layers):
                p = jax.tree.map(lambda a: a[i], params["blocks"])
                k, v = L.cross_kv(p["xattn"], mem, cfg)
                xk.append(k)
                xv.append(v)
        elif cfg.family == "vlm":
            mem = memory.astype(cfg.jnp_dtype)
            xk, xv = [], []
            for i in range(self._n_super):
                p = jax.tree.map(lambda a: a[i], params["super"])
                k, v = L.cross_kv(p["cross"]["xattn"], mem, cfg)
                xk.append(k)
                xv.append(v)
        else:
            return cache
        cache = dict(cache)
        cache["xk"] = jnp.stack(xk)
        cache["xv"] = jnp.stack(xv)
        return cache

    # -- input specs ---------------------------------------------------------

    def input_specs(self, shape_name: str) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for the step functions of this shape."""
        cfg = self.cfg
        sh = INPUT_SHAPES[shape_name]
        B, S = sh["global_batch"], sh["seq_len"]
        kind = sh["kind"]
        i32 = jnp.int32
        out: Dict[str, Any] = {}
        if kind == "train":
            out["tokens"] = jax.ShapeDtypeStruct((B, S + 1), i32)
        elif kind == "prefill":
            out["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        else:  # decode
            out["token"] = jax.ShapeDtypeStruct((B,), i32)
        if cfg.family == "audio" and kind != "decode":
            out["frames"] = jax.ShapeDtypeStruct((B, cfg.num_frames, cfg.d_model),
                                                 cfg.jnp_dtype)
        if cfg.family == "vlm" and kind != "decode":
            out["images"] = jax.ShapeDtypeStruct((B, cfg.num_image_tokens, cfg.d_model),
                                                 cfg.jnp_dtype)
        return out

    def param_count(self, params=None) -> int:
        if params is None:
            params, _ = self.init(abstract=True)
        return count_params(params)


def _sinusoid(length: int, dim: int) -> jax.Array:
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, dim, 2, dtype=jnp.float32) * (-math.log(10000.0) / dim))
    pe = jnp.zeros((length, dim), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
