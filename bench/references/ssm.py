"""Plain float32 reference of a Mamba-2 language model (Dao & Gu,
arXiv:2405.21060): per layer RMSNorm, in-projection to [z | x B C | dt],
causal depthwise convolution and SiLU on x B C, the SSD state-space layer,
the D skip, gated RMSNorm with z, out-projection; tied or untied head and
the per-sequence next-token cross entropy.

The SSD layer is computed in its quadratic (masked-attention) form, the
semiseparable matrix M[t, s] = C_t·B_s · exp(Σ_{s<u≤t} dt_u·A) · dt_s for
s ≤ t, and not by the chunked algorithm the program runs.  It imports
nothing of the program under test; ``init`` makes the weights the
benchmark hands to both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_EPS = 1e-5


def _dims(cfg):
    d_inner, H = cfg["ssm_d_inner"], cfg["ssm_heads"]
    G, N = cfg.get("ssm_groups", 1), cfg["ssm_state"]
    return d_inner, H, G, N, d_inner + 2 * G * N


def init(cfg: dict, key, dtype):
    """The parameter tree for ``cfg``; one call, traced under ``jax.jit``.
    A_log, D and dt_bias stay float32, as Mamba-2 keeps them."""
    L, d, V = cfg["n_layers"], cfg["d_model"], cfg["vocab"]
    d_inner, H, G, N, conv_ch = _dims(cfg)
    kc = cfg.get("conv_kernel", 4)
    ks = jax.random.split(key, 6)
    d_in = 2 * d_inner + 2 * G * N + H

    def dense(k, shape):
        std = shape[-2] ** -0.5
        return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)

    # dt = softplus(dt_bias) log-uniform on [1e-3, 1e-1], the Mamba-2 init
    dt = jnp.exp(jax.random.uniform(ks[0], (L, H)) * jnp.log(100.0) + jnp.log(1e-3))
    blk = {
        "mixer_norm": {"scale": jnp.ones((L, d), dtype)},
        "mamba": {
            "in_proj": dense(ks[1], (L, d, d_in)),
            "conv_w": (jax.random.normal(ks[2], (L, kc, conv_ch)) * 0.1).astype(dtype),
            "conv_b": jnp.zeros((L, conv_ch), dtype),
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)), (L, H)),
            "D": jnp.ones((L, H), jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm": jnp.ones((L, d_inner), dtype),
            "out_proj": dense(ks[3], (L, d_inner, d)),
        },
    }
    params = {
        "embed": (jax.random.normal(ks[4], (V, d), jnp.float32) * 0.02).astype(dtype),
        "blocks": (blk,),
        "final_norm": {"scale": jnp.ones((d,), dtype)},
    }
    if not cfg.get("tie_embeddings", False):
        params["lm_head"] = (jax.random.normal(ks[5], (d, V)) * 0.02).astype(dtype)
    return params


def rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + NORM_EPS) * scale


def ssd(x, dt, A, Bm, Cm):
    """y_t = Σ_{s≤t} C_t·B_s · exp(Σ_{s<u≤t} dt_u A) · dt_s · x_s.
    x: (B, S, H, P); dt: (B, S, H); A: (H,); Bm, Cm: (B, S, G, N)."""
    S, H = x.shape[1], x.shape[2]
    rep = H // Bm.shape[2]
    Bh, Ch = jnp.repeat(Bm, rep, axis=2), jnp.repeat(Cm, rep, axis=2)
    cs = jnp.cumsum(dt * A, axis=1)  # (B, S, H)
    diff = cs[:, :, None, :] - cs[:, None, :, :]  # (B, t, s, H)
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))  # 0 above the diagonal
    cb = jnp.einsum("bthn,bshn->btsh", Ch, Bh)
    return jnp.einsum("btsh,bshp->bthp", cb * decay, x * dt[..., None])


def _layer(cfg, mm, x, lp):
    Bsz, S, _ = x.shape
    d_inner, H, G, N, conv_ch = _dims(cfg)
    P = d_inner // H
    p = lp["mamba"]
    h = rms_norm(x, lp["mixer_norm"]["scale"])
    zxbcdt = mm(h, p["in_proj"])
    z, xbc, dt = zxbcdt[..., :d_inner], zxbcdt[..., d_inner:d_inner + conv_ch], zxbcdt[..., d_inner + conv_ch:]
    kc = p["conv_w"].shape[0]
    xp = jnp.pad(xbc, ((0, 0), (kc - 1, 0), (0, 0)))
    xbc = sum(xp[:, i:i + S] * p["conv_w"][i] for i in range(kc)) + p["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_inner].reshape(Bsz, S, H, P)
    Bm = xbc[..., d_inner:d_inner + G * N].reshape(Bsz, S, G, N)
    Cm = xbc[..., d_inner + G * N:].reshape(Bsz, S, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd(xs, dt, -jnp.exp(p["A_log"]), Bm, Cm) + p["D"][:, None] * xs
    y = rms_norm(y.reshape(Bsz, S, d_inner) * jax.nn.silu(z), p["norm"])
    return x + mm(y, p["out_proj"])


def seq_losses(cfg: dict, params, tokens, mm):
    """Mean next-token cross entropy of each row of ``tokens`` (B, S).
    ``mm`` is the matrix product of every projection and of the head."""
    x = params["embed"][tokens]

    def body(x, lp):
        return jax.checkpoint(lambda x, lp: _layer(cfg, mm, x, lp))(x, lp), None

    x, _ = jax.lax.scan(body, x, params["blocks"][0])
    x = rms_norm(x, params["final_norm"]["scale"])
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    logp = jax.nn.log_softmax(mm(x[:, :-1], head), axis=-1)
    ll = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(ll, axis=-1)


def forward_flops(cfg: dict, seq: int) -> float:
    """Model FLOPs of one forward pass over one sequence of ``seq`` tokens,
    each multiply-add counted as 2: per layer and position the projections,
    the depthwise convolution and the linear-time scan (state update x⊗B and
    read-out h·C, 2·P·N each per head); the head over the seq − 1 positions
    that have a next token.  The quadratic form computed above, the chunked
    form's intra-chunk work and elementwise work are left out, so the count
    is a lower bound on what any program computes."""
    d = cfg["d_model"]
    d_inner, H, G, N, conv_ch = _dims(cfg)
    in_proj = 2 * d * (2 * d_inner + 2 * G * N + H)
    out_proj = 2 * d_inner * d
    conv = 2 * cfg.get("conv_kernel", 4) * conv_ch
    scan = 2 * 2 * d_inner * N  # H·P = d_inner
    return cfg["n_layers"] * seq * (in_proj + out_proj + conv + scan) + 2 * d * cfg["vocab"] * (seq - 1)
