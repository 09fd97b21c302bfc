"""Config-driven LM assembly for all assigned architecture families.

One ``LM`` class covers dense GQA transformers, MoE, pure-SSM (mamba2),
hybrid (jamba), VLM and audio backbones via a per-layer *plan*:

    plan[l] = LayerSpec(mixer = "attn" | "mamba", mlp = "dense" | "moe" | "none")

Layers are stacked and executed with ``lax.scan`` over repeating *period
blocks* (period 1 for homogeneous stacks, 8 for jamba), which keeps HLO size
and compile time flat in depth — essential for dry-running 72-layer models.

Params are plain nested dicts; ``param_specs`` mirrors the structure with
PartitionSpecs (TP over 'model', optional FSDP over 'data').

Device work carries name scopes (DESIGN.md §10): ``embed``, ``layers`` (the
scanned stack's body) and ``head_loss`` (final norm, logits and the
cross-entropy).  Backward ops carry them under ``transpose(...)``.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.attention import attention_decode, attention_forward, init_attention
from repro.models.layers import embed_init, init_mlp, init_rms_norm, mlp, rms_norm
from repro.models.sharding import shard_batch

PyTree = Any


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str  # "attn" | "mamba"
    mlp: str  # "dense" | "moe" | "none"


def layer_plan(cfg: ModelConfig) -> tuple[LayerSpec, ...]:
    plan = []
    for l in range(cfg.n_layers):
        if cfg.family == "ssm":
            mixer = "mamba"
        elif cfg.family == "hybrid":
            mixer = "attn" if (l % cfg.attn_period) == cfg.attn_offset else "mamba"
        else:
            mixer = "attn"
        if cfg.family in ("moe",):
            m = "moe" if (l % cfg.moe_every) == (cfg.moe_every - 1) else "dense"
        elif cfg.family == "hybrid" and cfg.n_experts:
            m = "moe" if (l % cfg.moe_every) == (cfg.moe_every - 1) else "dense"
        elif cfg.family == "ssm":
            m = "none" if cfg.d_ff == 0 else "dense"
        else:
            m = "dense"
        plan.append(LayerSpec(mixer, m))
    return tuple(plan)


def plan_period(plan: tuple[LayerSpec, ...]) -> int:
    """Smallest p dividing len(plan) with plan repeating at period p."""
    L = len(plan)
    for p in range(1, L + 1):
        if L % p == 0 and all(plan[i] == plan[i % p] for i in range(L)):
            return p
    return L


def _dtype(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.plan = layer_plan(cfg)
        self.period = plan_period(self.plan)
        self.n_rep = cfg.n_layers // self.period

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------

    def _init_block(self, rng: jax.Array, spec: LayerSpec) -> PyTree:
        cfg = self.cfg
        dt = _dtype(cfg)
        ks = jax.random.split(rng, 4)
        blk: dict[str, Any] = {"mixer_norm": init_rms_norm(cfg.d_model, dt)}
        if spec.mixer == "attn":
            blk["attn"] = init_attention(
                ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
                cfg.qkv_bias, dt,
            )
        else:
            blk["mamba"] = ssm_lib.init_mamba(
                ks[0], cfg.d_model, d_inner=cfg.ssm_d_inner, n_heads=cfg.ssm_heads,
                d_state=cfg.ssm_state, n_groups=cfg.ssm_groups,
                conv_kernel=cfg.conv_kernel, dtype=dt,
            )
        if spec.mlp != "none":
            blk["mlp_norm"] = init_rms_norm(cfg.d_model, dt)
            if spec.mlp == "moe":
                blk["moe"] = moe_lib.init_moe(ks[1], cfg.d_model, cfg.n_experts, cfg.expert_d_ff, dt)
            else:
                blk["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, dt)
        return blk

    def init(self, rng: jax.Array) -> PyTree:
        cfg = self.cfg
        dt = _dtype(cfg)
        k_embed, k_blocks, k_head = jax.random.split(rng, 3)
        params: dict[str, Any] = {}
        if cfg.frontend != "audio":
            params["embed"] = embed_init(k_embed, (cfg.vocab, cfg.d_model), dt)
        blocks = []
        for j in range(self.period):
            keys = jax.random.split(jax.random.fold_in(k_blocks, j), self.n_rep)
            blocks.append(jax.vmap(lambda k, j=j: self._init_block(k, self.plan[j]))(keys))
        params["blocks"] = tuple(blocks)
        params["final_norm"] = init_rms_norm(cfg.d_model, dt)
        if not cfg.tie_embeddings or cfg.frontend == "audio":
            params["lm_head"] = embed_init(k_head, (cfg.d_model, cfg.vocab), dt)
        return params

    def param_count(self, params: PyTree) -> int:
        return sum(int(math.prod(x.shape)) for x in jax.tree.leaves(params))

    # ------------------------------------------------------------------
    # blocks
    # ------------------------------------------------------------------

    def _apply_block(
        self, j: int, bp: PyTree, x: jnp.ndarray, positions: jnp.ndarray,
        cache: PyTree | None, mode: str, pos_scalar: jnp.ndarray | None,
    ) -> tuple[jnp.ndarray, jnp.ndarray, PyTree]:
        """Returns (x, aux_loss, new_cache); a MoE layer's aux is per
        sequence, shape (B,), every other layer's the scalar zero."""
        cfg = self.cfg
        spec = self.plan[j]
        aux = jnp.zeros((), jnp.float32)
        h = rms_norm(x, bp["mixer_norm"]["scale"], cfg.norm_eps)
        new_cache: dict[str, Any] = {}
        if spec.mixer == "attn":
            kw = dict(
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.resolved_head_dim,
                rotary_dim=cfg.rotary_dim, rope_theta=cfg.rope_theta, window=cfg.window,
            )
            if mode == "decode":
                out, new_cache = attention_decode(bp["attn"], h, cache, pos_scalar, **kw)
            else:
                out, c = attention_forward(
                    bp["attn"], h, positions, causal=cfg.causal,
                    return_cache=(mode == "prefill"),
                    cache_len=(cache if isinstance(cache, int) else None), **kw,
                )
                new_cache = c or {}
        else:
            kw = dict(
                d_inner=cfg.ssm_d_inner, n_heads=cfg.ssm_heads, d_state=cfg.ssm_state,
                n_groups=cfg.ssm_groups,
            )
            if mode == "decode":
                out, new_cache = ssm_lib.mamba_decode(bp["mamba"], h, cache, **kw)
            else:
                out, c = ssm_lib.mamba_forward(
                    bp["mamba"], h, chunk=cfg.ssm_chunk,
                    return_cache=(mode == "prefill"), **kw,
                )
                new_cache = c or {}
        x = x + out
        if spec.mlp != "none":
            h = rms_norm(x, bp["mlp_norm"]["scale"], cfg.norm_eps)
            if spec.mlp == "moe":
                moe_fn = (
                    moe_lib.moe_apply_dense if cfg.moe_dispatch == "dense" else moe_lib.moe_apply
                )
                y, a = jax.vmap(
                    lambda hh: moe_fn(
                        bp["moe"], hh, top_k=cfg.top_k,
                        capacity_factor=cfg.capacity_factor, act=cfg.act,
                    )
                )(h)
                aux = aux + a  # per sequence: the coded loss stays Σ_b w_b·loss_b
            else:
                y = mlp(bp["mlp"], h, cfg.act)
            x = x + y
        return x, aux, new_cache

    def _run_stack(
        self, params: PyTree, x: jnp.ndarray, positions: jnp.ndarray,
        mode: str, caches: PyTree | None = None,
        pos_scalar: jnp.ndarray | None = None, cache_len: int | None = None,
    ) -> tuple[jnp.ndarray, jnp.ndarray, PyTree | None]:
        cfg = self.cfg

        def body(carry, xs):
            x, aux = carry
            bps = xs[0]
            cbs = xs[1] if len(xs) > 1 else (None,) * self.period
            new_caches = []
            with jax.named_scope("layers"):
                for j in range(self.period):
                    cj = cbs[j] if cbs[j] is not None and len(cbs[j]) else (cache_len if mode == "prefill" else None)
                    x = shard_batch(x)  # re-anchor DP sharding each block
                    x, a, nc = self._apply_block(j, bps[j], x, positions, cj, mode, pos_scalar)
                    aux = aux + a
                    new_caches.append(nc)
            return (x, aux), tuple(new_caches)

        if cfg.remat == "full" and mode == "train":
            body = jax.checkpoint(body, prevent_cse=False)

        xs = (params["blocks"],) if caches is None else (params["blocks"], caches)
        # a stack with a MoE layer carries its load-balance loss per sequence
        has_moe = any(spec.mlp == "moe" for spec in self.plan)
        aux0 = jnp.zeros((x.shape[0],) if has_moe else (), jnp.float32)
        (x, aux), new_caches = jax.lax.scan(body, (x, aux0), xs)
        return x, aux, (new_caches if mode in ("prefill", "decode") else None)

    # ------------------------------------------------------------------
    # embedding / head
    # ------------------------------------------------------------------

    def _embed(self, params: PyTree, batch: PyTree) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (x (B,S,d), label_mask_offset handled by caller)."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            if cfg.frontend == "audio":
                return batch["frames"].astype(_dtype(cfg))
            # taken from an f32 view, the lookup's gradient (a scatter-add over
            # the batch's tokens) sums a token's repeats in f32: the TPU's bf16
            # scatter-add loses most of a token that repeats hundreds of times
            table = params["embed"]
            tok = jnp.take(table.astype(jnp.float32), batch["tokens"], axis=0).astype(table.dtype)
            if cfg.frontend == "vision":
                return jnp.concatenate([batch["patches"].astype(tok.dtype), tok], axis=1)
            return tok

    def _logits(self, params: PyTree, x: jnp.ndarray) -> jnp.ndarray:
        head = params["lm_head"] if "lm_head" in params else params["embed"].T
        return x @ head

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def forward(self, params: PyTree, batch: PyTree) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Full forward.  Returns (logits (B, S_total, V), aux_loss): the
        MoE load-balance loss per sequence (B,), or the scalar zero for a
        stack with no MoE layer."""
        x = self._embed(params, batch)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        x, aux, _ = self._run_stack(params, x, positions, "train")
        with jax.named_scope("head_loss"):
            x = rms_norm(x, params["final_norm"]["scale"], self.cfg.norm_eps)
            return self._logits(params, x), aux

    def seq_losses(self, params: PyTree, batch: PyTree) -> jnp.ndarray:
        """Per-sequence mean CE (+ per-seq MoE aux), shape (B,).

        Gradient coding needs per-*partition* losses whose weighted sum the
        code decodes; everything here is per-sequence so the encode/decode
        algebra is exact (see core/aggregator.py).
        """
        cfg = self.cfg
        logits, aux = self.forward(params, batch)
        with jax.named_scope("head_loss"):
            labels = batch["labels"]
            if cfg.frontend == "vision":
                # patch positions carry no labels; text span starts at n_patches
                logits = logits[:, cfg.n_patches :]
            if not cfg.encoder_only:
                logits, labels = logits[:, :-1], labels[:, 1:]
            valid = labels >= 0
            lab = jnp.where(valid, labels, 0)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            ll = jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
            ce = -jnp.sum(ll * valid, axis=-1) / jnp.maximum(jnp.sum(valid, axis=-1), 1)
            return ce + cfg.aux_coef * aux

    def weighted_loss(self, params: PyTree, batch: PyTree) -> jnp.ndarray:
        """Σ_b weight_b · seq_loss_b — the coded-DP training objective."""
        losses = self.seq_losses(params, batch)
        with jax.named_scope("head_loss"):
            return jnp.sum(losses * batch["weight"])

    def prefill(self, params: PyTree, batch: PyTree, cache_len: int) -> tuple[jnp.ndarray, PyTree]:
        """Returns (last-position logits (B, V), cache)."""
        x = self._embed(params, batch)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        x, _, caches = self._run_stack(params, x, positions, "prefill", cache_len=cache_len)
        x = rms_norm(x, params["final_norm"]["scale"], self.cfg.norm_eps)
        logits = self._logits(params, x[:, -1])
        return logits, {"layers": caches, "pos": jnp.asarray(x.shape[1], jnp.int32)}

    def decode_step(self, params: PyTree, tokens: jnp.ndarray, cache: PyTree) -> tuple[jnp.ndarray, PyTree]:
        """One token.  tokens: (B, 1) int32.  Returns (logits (B, V), cache).

        ``cache["pos"]`` may be a scalar (all rows at the same position — the
        classic batched path) or an (B,) vector (slot-indexed continuous
        batching: each row decodes at its own position).
        """
        x = jnp.take(params["embed"], tokens, axis=0) if "embed" in params else tokens
        pos = cache["pos"]
        positions = jnp.atleast_1d(pos).astype(jnp.int32)
        x, _, new_caches = self._run_stack(
            params, x, positions, "decode", caches=cache["layers"], pos_scalar=pos
        )
        x = rms_norm(x, params["final_norm"]["scale"], self.cfg.norm_eps)
        return self._logits(params, x[:, 0]), {"layers": new_caches, "pos": pos + 1}

    # ------------------------------------------------------------------
    # slot-indexed cache ops (continuous-batching serving, DESIGN.md §9)
    # ------------------------------------------------------------------

    def empty_slot_cache(self, params: PyTree, n_slots: int, cache_len: int) -> PyTree:
        """Zeroed decode cache for ``n_slots`` independent requests with a
        per-slot ``pos`` vector.  Shapes come from ``eval_shape`` on prefill,
        so every family (KV ring, SSM state, conv ring) is covered without
        enumerating cache layouts here."""
        if self.cfg.encoder_only:
            raise ValueError(f"{self.cfg.name} is encoder-only; no decode cache")
        dummy = {"tokens": jnp.zeros((n_slots, 1), jnp.int32)}
        if self.cfg.frontend == "vision":
            dummy["patches"] = jnp.zeros(
                (n_slots, self.cfg.n_patches, self.cfg.d_model), jnp.float32
            )
        _, cache_shape = jax.eval_shape(
            partial(self.prefill, cache_len=cache_len), params, dummy
        )
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_shape)
        return {"layers": cache["layers"], "pos": jnp.zeros((n_slots,), jnp.int32)}

    @staticmethod
    def cache_insert_slot(batch_cache: PyTree, req_cache: PyTree, slot: jnp.ndarray) -> PyTree:
        """Write a single-request prefill cache (batch dim 1) into ``slot``
        of a slot cache — the op that lets a new request join a running
        decode batch without retracing.  Layer leaves are scan-stacked
        (n_rep, B, ...), so the batch dim is axis 1."""
        layers = jax.tree.map(
            lambda big, one: big.at[:, slot].set(one[:, 0].astype(big.dtype)),
            batch_cache["layers"], req_cache["layers"],
        )
        pos = batch_cache["pos"].at[slot].set(req_cache["pos"].astype(jnp.int32))
        return {"layers": layers, "pos": pos}

    @staticmethod
    def cache_evict_slot(batch_cache: PyTree, slot: jnp.ndarray) -> PyTree:
        """Zero one slot (finished/cancelled request).  Decode math never
        reads an inactive slot's values (its outputs are masked), but a zero
        slot keeps stale state from leaking NaN/Inf into reductions."""
        layers = jax.tree.map(
            lambda big: big.at[:, slot].set(jnp.zeros_like(big[:, slot])),
            batch_cache["layers"],
        )
        return {"layers": layers, "pos": batch_cache["pos"].at[slot].set(0)}

    # ------------------------------------------------------------------
    # sharding
    # ------------------------------------------------------------------

    def _block_specs(self, spec: LayerSpec, tp: str, moe_tp_on_experts: bool) -> PyTree:
        cfg = self.cfg
        n = lambda *dims: P(*((None,) + dims))  # prepend stacked-layer dim
        blk: dict[str, Any] = {"mixer_norm": {"scale": n(None)}}
        if spec.mixer == "attn":
            a = {"wq": n(None, tp), "wk": n(None, tp), "wv": n(None, tp), "wo": n(tp, None)}
            if cfg.qkv_bias:
                a |= {"bq": n(tp), "bk": n(tp), "bv": n(tp)}
            blk["attn"] = a
        else:
            blk["mamba"] = {
                "in_proj": n(None, tp), "conv_w": n(None, tp), "conv_b": n(tp),
                "A_log": n(tp), "D": n(tp), "dt_bias": n(tp), "norm": n(tp),
                "out_proj": n(tp, None),
            }
        if spec.mlp == "dense":
            blk["mlp_norm"] = {"scale": n(None)}
            blk["mlp"] = {"w_gate": n(None, tp), "w_up": n(None, tp), "w_down": n(tp, None)}
        elif spec.mlp == "moe":
            blk["mlp_norm"] = {"scale": n(None)}
            if moe_tp_on_experts:
                blk["moe"] = {
                    "router": n(None, None),
                    "w_gate": n(tp, None, None), "w_up": n(tp, None, None),
                    "w_down": n(tp, None, None),
                }
            else:
                blk["moe"] = {
                    "router": n(None, None),
                    "w_gate": n(None, None, tp), "w_up": n(None, None, tp),
                    "w_down": n(None, tp, None),
                }
        return blk

    def param_specs(self, tp_axis: str = "model", tp_size: int = 16) -> PyTree:
        cfg = self.cfg
        moe_on_experts = cfg.n_experts > 0 and cfg.n_experts % tp_size == 0
        # odd vocabularies (50280, 92553, 504) cannot shard the vocab dim at
        # tp=16 — shard the d_model dim of the embedding/head instead
        vocab_ok = cfg.vocab % tp_size == 0
        specs: dict[str, Any] = {}
        if cfg.frontend != "audio":
            specs["embed"] = P(tp_axis, None) if vocab_ok else P(None, tp_axis)
        specs["blocks"] = tuple(
            self._block_specs(self.plan[j], tp_axis, moe_on_experts) for j in range(self.period)
        )
        specs["final_norm"] = {"scale": P(None)}
        if not cfg.tie_embeddings or cfg.frontend == "audio":
            specs["lm_head"] = P(None, tp_axis) if vocab_ok else P(tp_axis, None)
        return specs

    def fsdp_specs(
        self, param_shapes: PyTree, base_specs: PyTree,
        fsdp_axis: str = "data", fsdp_size: int = 16,
    ) -> PyTree:
        """ZeRO-style extension: add ``fsdp_axis`` on the first unsharded,
        divisible dim of every tensor.  Applied to optimizer state (and,
        for the largest models, the params themselves) so per-device bytes
        scale with 1/(tp·dp) instead of 1/tp."""

        def extend(leaf, spec):
            dims = list(spec) + [None] * (leaf.ndim - len(spec))
            for i, d in enumerate(leaf.shape):
                if dims[i] is None and d % fsdp_size == 0 and d >= fsdp_size:
                    dims[i] = fsdp_axis
                    return P(*dims)
            return P(*dims)

        return jax.tree.map(extend, param_shapes, base_specs,
                            is_leaf=lambda x: isinstance(x, P))


def build_model(cfg: ModelConfig) -> LM:
    return LM(cfg)
