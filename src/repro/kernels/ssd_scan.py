"""Pallas TPU kernel: mamba2 SSD chunked scan.

Maps the SSD decomposition (Dao & Gu) onto the MXU instead of a length-S
sequential scan:

  per chunk of length L (grid dim, sequential):
    intra-chunk:  Y_d = (C·Bᵀ ⊙ decay_mask) · X          — two (L×N)(N×L),
                                                            (L×L)(L×P) matmuls
    state input:  Y_o = exp(cumsum a) ⊙ (C · hᵀ)          — (L×N)(N×P)
    state update: h' = exp(Σa)·h + (B ⊙ tail-decay)ᵀ · X  — (N×L)(L×P)

  the state h (P, N) lives in f32 VMEM scratch and persists across the
  sequential chunk dimension — the recurrence never touches HBM.

Grid (B·H, n_chunks).  Block shapes: x (1, L, P), dA (1, L), B/C (1, L, N);
with L=128, P=64, N=128 all four matmuls are full MXU tiles and the VMEM
working set is ~0.3 MiB.  Groups are broadcast to heads in the wrapper
(G≠H costs only index_map arithmetic, not memory: same trick as GQA).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_scr, *, n_chunks):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)  # (L, P)
    a = a_ref[0].astype(jnp.float32)  # (L,)
    Bm = b_ref[0].astype(jnp.float32)  # (L, N)
    Cm = c_ref[0].astype(jnp.float32)  # (L, N)
    L = x.shape[0]

    a_cum = jnp.cumsum(a)  # (L,)
    # decay_mask[i, j] = exp(sum_{j<t<=i} a_t) for i >= j else 0
    seg = a_cum[:, None] - a_cum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    mask = ii >= jj
    decay = jnp.where(mask, jnp.exp(jnp.where(mask, seg, 0.0)), 0.0)

    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(CB * decay, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    h = h_scr[...]  # (P, N)
    # carried-state contribution: exp(a_cum)[:,None] * (C @ h^T)
    Ch = jax.lax.dot_general(Cm, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)  # (L, P)
    y = y + jnp.exp(a_cum)[:, None] * Ch
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: h' = exp(sum a) * h + x^T @ (B * exp(a_sum - a_cum))
    tail = jnp.exp(a_cum[-1] - a_cum)  # (L,)
    xB = jax.lax.dot_general(
        x, Bm * tail[:, None], (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (P, N)
    h_scr[...] = jnp.exp(a_cum[-1]) * h + xB

    @pl.when(ci == n_chunks - 1)
    def _writeout():
        hout_ref[0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(
    x: jnp.ndarray,  # (B, S, H, P) — pre-multiplied by dt
    dA: jnp.ndarray,  # (B, S, H)
    Bm: jnp.ndarray,  # (B, S, G, N)
    Cm: jnp.ndarray,  # (B, S, G, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (B,S,H,P), h_final (B,H,P,N))."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    rep = H // G

    xh = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    ah = dA.transpose(0, 2, 1).reshape(B * H, S)
    bh = Bm.transpose(0, 2, 1, 3).reshape(B * G, S, N)
    ch = Cm.transpose(0, 2, 1, 3).reshape(B * G, S, N)

    kernel = functools.partial(_ssd_kernel, n_chunks=nc)

    def bc_map(h, c, rep=rep, G=G):
        # head index -> (batch, group) row in the (B*G, S, N) layout
        return ((h // (G * rep)) * G + (h % (G * rep)) // rep, c, 0)

    y, hout = pl.pallas_call(
        kernel,
        name="ssd_scan",
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk), lambda h, c: (h, c)),
            pl.BlockSpec((1, chunk, N), bc_map),
            pl.BlockSpec((1, chunk, N), bc_map),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, P, N), lambda h, c: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B * H, P, N), jnp.float32),
        ],
        scratch_shapes=[_vmem_scratch(P, N)],
        interpret=interpret,
    )(xh, ah, bh, ch)
    y = y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    h = hout.reshape(B, H, P, N)
    return y, h


def _vmem_scratch(r: int, c: int):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM((r, c), jnp.float32)
