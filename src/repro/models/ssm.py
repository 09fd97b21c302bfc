"""Mamba2 (SSD — state-space duality) blocks, pure-jnp reference path.

Implements the chunked SSD algorithm of Dao & Gu (arXiv:2405.21060): the
sequence is split into chunks; within-chunk terms are dense matmuls
(MXU-friendly "attention-like" form), across-chunk terms use a short state
recurrence — O(S·N·P) instead of a length-S sequential scan.  The Pallas
kernel in kernels/ssd_scan mirrors this decomposition; this module is the
oracle it is validated against and the lowering path used by dry-runs.

Layer structure follows mamba2:
  in_proj -> [z | xBC | dt];  causal depthwise conv on xBC;  SSD(x, dt, A, B, C);
  y = y + D*x;  gated RMSNorm with z;  out_proj.

Decode keeps O(1) state per layer: the SSM state h (B, H, P, N) plus the
conv ring (B, k-1, channels) — this is why SSM/hybrid archs run long_500k.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, rms_norm

PyTree = Any


def init_mamba(
    rng: jax.Array,
    d_model: int,
    *,
    d_inner: int,
    n_heads: int,
    d_state: int,
    n_groups: int = 1,
    conv_kernel: int = 4,
    dtype=jnp.float32,
) -> PyTree:
    k1, k2, k3 = jax.random.split(rng, 3)
    conv_ch = d_inner + 2 * n_groups * d_state
    d_in_proj = 2 * d_inner + 2 * n_groups * d_state + n_heads
    # dt bias init so softplus(dt_bias) spans ~[1e-3, 1e-1] (mamba2 default)
    dt = jnp.exp(
        jax.random.uniform(k3, (n_heads,), jnp.float32)
        * (jnp.log(0.1) - jnp.log(0.001))
        + jnp.log(0.001)
    )
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus
    return {
        "in_proj": dense_init(k1, (d_model, d_in_proj), dtype),
        "conv_w": (jax.random.normal(k2, (conv_kernel, conv_ch), jnp.float32) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.log(jnp.arange(1, n_heads + 1, dtype=jnp.float32)),
        "D": jnp.ones((n_heads,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "norm": jnp.ones((d_inner,), dtype),
        "out_proj": dense_init(jax.random.fold_in(rng, 7), (d_inner, d_model), dtype),
    }


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------


def _segsum(x: jnp.ndarray) -> jnp.ndarray:
    """(..., L) -> (..., L, L) lower-triangular pairwise cumulative sums:
    out[i, j] = sum_{j < t <= i} x[t]  (i >= j), -inf above the diagonal."""
    L = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool))
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jnp.ndarray,  # (B, S, H, P) — already multiplied by dt
    dA: jnp.ndarray,  # (B, S, H)   — dt * A (negative log-decay increments)
    Bm: jnp.ndarray,  # (B, S, G, N)
    Cm: jnp.ndarray,  # (B, S, G, N)
    chunk: int,
    h0: jnp.ndarray | None = None,  # (B, H, P, N) initial state
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD.  Returns (y (B,S,H,P), final state (B,H,P,N)).

    Head h reads group h // R of B and C (R = rep = H // G).  The heads are
    viewed as (G, R), so C·Bᵀ is built once per group and B, C are never
    copied to every head; only the decay is per head."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert S % chunk == 0, f"seq {S} not divisible by chunk {chunk}"
    rep = H // G
    nc = S // chunk
    f32 = jnp.float32

    xc = x.reshape(B, nc, chunk, G, rep, P).astype(f32)
    ac = dA.reshape(B, nc, chunk, G, rep).transpose(0, 3, 4, 1, 2).astype(f32)  # (B,G,R,nc,L)
    Bc = Bm.reshape(B, nc, chunk, G, N).astype(f32)
    Cc = Cm.reshape(B, nc, chunk, G, N).astype(f32)
    # (B,G,R,nc,L) -> (B,nc,L,G,R,1): a per-head factor along x's layout
    along_x = lambda t: t.transpose(0, 3, 4, 1, 2)[..., None]

    a_cum = jnp.cumsum(ac, axis=-1)  # (B,G,R,nc,L)
    Lmat = jnp.exp(_segsum(ac))  # (B,G,R,nc,L,L)

    # 1. intra-chunk (diagonal blocks): attention-like dense matmuls
    CB = jnp.einsum("bclgn,bcsgn->bgcls", Cc, Bc)  # (B,G,nc,L,L): once per group
    y_diag = jnp.einsum("bgrcls,bcsgrp->bclgrp", CB[:, :, None] * Lmat, xc)

    # 2. per-chunk final states
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)  # (B,G,R,nc,L)
    states = jnp.einsum("bclgn,bclgrp->bcgrpn", Bc, xc * along_x(decay_states))

    # 3. inter-chunk recurrence (prefix over nc chunk states)
    chunk_decay = jnp.exp(a_cum[..., -1])  # (B,G,R,nc)

    def chunk_step(h, inp):
        st, dec = inp  # (B,G,R,P,N), (B,G,R)
        h_out = h  # state entering this chunk
        h = h * dec[..., None, None] + st
        return h, h_out

    h_init = (
        jnp.zeros((B, G, rep, P, N), f32)
        if h0 is None
        else h0.reshape(B, G, rep, P, N).astype(f32)
    )
    states_t = states.transpose(1, 0, 2, 3, 4, 5)  # (nc,B,G,R,P,N)
    decay_t = chunk_decay.transpose(3, 0, 1, 2)  # (nc,B,G,R)
    h_final, h_in = jax.lax.scan(chunk_step, h_init, (states_t, decay_t))
    h_in = h_in.transpose(1, 0, 2, 3, 4, 5)  # (B,nc,G,R,P,N): state entering chunk c

    # 4. off-diagonal contribution from carried state
    state_decay = jnp.exp(a_cum)  # (B,G,R,nc,L)
    y_off = jnp.einsum("bclgn,bcgrpn->bclgrp", Cc, h_in) * along_x(state_decay)

    y = (y_diag + y_off).reshape(B, S, H, P)
    return y, h_final.reshape(B, H, P, N)


def ssd_sequential(x, dA, Bm, Cm, h0=None):
    """O(S) sequential oracle (used only in tests to validate ssd_chunked)."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    f32 = jnp.float32
    Bh = jnp.repeat(Bm, rep, axis=2).astype(f32)
    Ch = jnp.repeat(Cm, rep, axis=2).astype(f32)
    h = jnp.zeros((B, H, P, N), f32) if h0 is None else h0.astype(f32)

    def step(h, t):
        a = jnp.exp(dA[:, t]).astype(f32)  # (B,H)
        h = h * a[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", x[:, t].astype(f32), Bh[:, t]
        )
        y = jnp.einsum("bhpn,bhn->bhp", h, Ch[:, t])
        return h, y

    h, ys = jax.lax.scan(step, h, jnp.arange(S))
    return ys.transpose(1, 0, 2, 3), h


# ---------------------------------------------------------------------------
# full mamba2 block
# ---------------------------------------------------------------------------


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, state: jnp.ndarray | None):
    """Depthwise causal conv1d.  x: (B, S, C), w: (k, C).  state: (B, k-1, C)
    carries the last k-1 inputs for decode."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)  # (B, S+k-1, C)
    out = sum(xp[:, i : i + x.shape[1]] * w[i][None, None] for i in range(k)) + b
    new_state = xp[:, -(k - 1) :] if k > 1 else None
    return out, new_state


def mamba_forward(
    params: PyTree,
    x: jnp.ndarray,
    *,
    d_inner: int,
    n_heads: int,
    d_state: int,
    n_groups: int = 1,
    chunk: int = 64,
    return_cache: bool = False,
) -> tuple[jnp.ndarray, PyTree | None]:
    """Train/prefill.  x: (B, S, d_model)."""
    B, S, _ = x.shape
    P = d_inner // n_heads
    zxbcdt = x @ params["in_proj"]
    z, xBC, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * n_groups * d_state], axis=-1)
    xBC, conv_state = _causal_conv(xBC, params["conv_w"], params["conv_b"], None)
    xBC = jax.nn.silu(xBC)
    xs, Bm, Cm = jnp.split(xBC, [d_inner, d_inner + n_groups * d_state], axis=-1)
    xs = xs.reshape(B, S, n_heads, P)
    Bm = Bm.reshape(B, S, n_groups, d_state)
    Cm = Cm.reshape(B, S, n_groups, d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,S,H)
    A = -jnp.exp(params["A_log"])  # (H,)
    # zero-pad to a chunk multiple: x=0 adds nothing to the state and dA=0
    # gives decay exp(0)=1, so padding is exact
    pad = (-S) % chunk
    with jax.named_scope("ssd"):
        if pad:
            zp = lambda t: jnp.pad(t, [(0, pad if i == 1 else 0) for i in range(t.ndim)])
            y, h = ssd_chunked(zp(xs * dt[..., None]), zp(dt * A), zp(Bm), zp(Cm), chunk)
            y = y[:, :S]
        else:
            y, h = ssd_chunked(xs * dt[..., None], dt * A, Bm, Cm, chunk)
    y = y + params["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(B, S, d_inner).astype(x.dtype)
    # gated RMSNorm (mamba2): norm(y * silu(z))
    y = rms_norm(y * jax.nn.silu(z), params["norm"], 1e-5)
    out = y @ params["out_proj"]
    cache = None
    if return_cache:
        cache = {"h": h.astype(jnp.float32), "conv": conv_state.astype(x.dtype)}
    return out, cache


def mamba_decode(
    params: PyTree,
    x: jnp.ndarray,
    cache: PyTree,
    *,
    d_inner: int,
    n_heads: int,
    d_state: int,
    n_groups: int = 1,
) -> tuple[jnp.ndarray, PyTree]:
    """One-token decode.  x: (B, 1, d_model); cache: {"h", "conv"}."""
    B = x.shape[0]
    P = d_inner // n_heads
    zxbcdt = x @ params["in_proj"]
    z, xBC, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * n_groups * d_state], axis=-1)
    xBC, conv_state = _causal_conv(xBC, params["conv_w"], params["conv_b"], cache["conv"])
    xBC = jax.nn.silu(xBC)
    xs, Bm, Cm = jnp.split(xBC, [d_inner, d_inner + n_groups * d_state], axis=-1)
    xs = xs.reshape(B, n_heads, P).astype(jnp.float32)  # S=1 squeezed
    Bm = jnp.repeat(Bm.reshape(B, n_groups, d_state), n_heads // n_groups, axis=1)
    Cm = jnp.repeat(Cm.reshape(B, n_groups, d_state), n_heads // n_groups, axis=1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)[:, 0] + params["dt_bias"])  # (B,H)
    A = -jnp.exp(params["A_log"])
    a = jnp.exp(dt * A)  # (B,H)
    h = cache["h"] * a[..., None, None] + jnp.einsum(
        "bhp,bhn->bhpn", xs * dt[..., None], Bm.astype(jnp.float32)
    )
    y = jnp.einsum("bhpn,bhn->bhp", h, Cm.astype(jnp.float32))
    y = y + params["D"][None, :, None] * xs
    y = y.reshape(B, 1, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["norm"], 1e-5)
    return y @ params["out_proj"], {"h": h, "conv": conv_state}
