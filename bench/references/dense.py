"""Plain float32 reference of a dense decoder-only transformer of the Llama
layer layout (SmolLM): RMSNorm, grouped-query attention with rotary
positions (half-split rotation), SwiGLU MLP, tied or untied head, and the
per-sequence next-token cross entropy.

It imports nothing of the program under test.  ``init`` makes the weights
(the benchmark hands the same tree to the program, in the configuration's
dtype), ``seq_losses`` computes the loss from them.  The tree's key names
are the program's parameter layout: layers stacked on a leading axis under
``blocks[0]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_EPS = 1e-5


def _dense(key, shape, dtype):
    """Truncated normal, std 1/sqrt(fan_in)."""
    std = shape[-2] ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def init(cfg: dict, key, dtype):
    """The parameter tree for ``cfg``; one call, traced under ``jax.jit``."""
    L, d, ff, V = cfg["n_layers"], cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    H, K = cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg.get("head_dim") or d // H
    ks = jax.random.split(key, 8)
    ones = jnp.ones((L, d), dtype)
    blk = {
        "mixer_norm": {"scale": ones},
        "attn": {
            "wq": _dense(ks[0], (L, d, H * hd), dtype),
            "wk": _dense(ks[1], (L, d, K * hd), dtype),
            "wv": _dense(ks[2], (L, d, K * hd), dtype),
            "wo": _dense(ks[3], (L, H * hd, d), dtype),
        },
        "mlp_norm": {"scale": ones},
        "mlp": {
            "w_gate": _dense(ks[4], (L, d, ff), dtype),
            "w_up": _dense(ks[5], (L, d, ff), dtype),
            "w_down": _dense(ks[6], (L, ff, d), dtype),
        },
    }
    params = {
        "embed": (jax.random.normal(ks[7], (V, d), jnp.float32) * 0.02).astype(dtype),
        "blocks": (blk,),
        "final_norm": {"scale": jnp.ones((d,), dtype)},
    }
    if not cfg.get("tie_embeddings", False):
        params["lm_head"] = (
            jax.random.normal(jax.random.fold_in(key, 9), (d, V), jnp.float32) * 0.02
        ).astype(dtype)
    return params


def rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + NORM_EPS) * scale


def rope(x, theta):
    """x: (B, S, heads, hd); rotates the two halves of the head dim."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(cfg, mm, x, lp):
    B, S, d = x.shape
    H, K = cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg.get("head_dim") or d // H
    a = lp["attn"]
    h = rms_norm(x, lp["mixer_norm"]["scale"])
    q = rope(mm(h, a["wq"]).reshape(B, S, H, hd), cfg.get("rope_theta", 10000.0))
    k = rope(mm(h, a["wk"]).reshape(B, S, K, hd), cfg.get("rope_theta", 10000.0))
    v = mm(h, a["wv"]).reshape(B, S, K, hd)
    k = jnp.repeat(k, H // K, axis=2)  # query head i reads kv head i // (H/K)
    v = jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + mm(o.reshape(B, S, H * hd), a["wo"])
    m = lp["mlp"]
    h = rms_norm(x, lp["mlp_norm"]["scale"])
    return x + mm(jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_up"]), m["w_down"])


def seq_losses(cfg: dict, params, tokens, mm):
    """Mean next-token cross entropy of each row of ``tokens`` (B, S).

    ``mm(x, w)`` is the matrix product of every linear layer, so a caller
    can compute them in another precision; all else is float32."""
    x = params["embed"][tokens]

    def body(x, lp):
        return jax.checkpoint(lambda x, lp: _layer(cfg, mm, x, lp))(x, lp), None

    x, _ = jax.lax.scan(body, x, params["blocks"][0])
    x = rms_norm(x, params["final_norm"]["scale"])
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    logits = mm(x[:, :-1], head)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(ll, axis=-1)


def forward_flops(cfg: dict, seq: int) -> float:
    """Model FLOPs of one forward pass over one sequence of ``seq`` tokens,
    each multiply-add counted as 2: the matrix products of every layer, the
    causal half of attention (position t attends to t+1 keys, QK^T and PV
    2·head_dim each per key), and the head over the seq − 1 positions that
    have a next token.  Elementwise work (norms, softmax, activations) is
    left out, so the count is a lower bound on what any program computes."""
    d, H, K, ff = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["d_ff"]
    hd = cfg.get("head_dim") or d // H
    proj = 2 * d * (H * hd + 2 * K * hd) + 2 * H * hd * d
    attn = 2 * 2 * H * hd * seq * (seq + 1) / 2
    mlp = 3 * 2 * d * ff
    return cfg["n_layers"] * (seq * (proj + mlp) + attn) + 2 * d * cfg["vocab"] * (seq - 1)
