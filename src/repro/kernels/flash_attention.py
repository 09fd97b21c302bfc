"""Pallas TPU kernel: flash attention fwd (causal / sliding-window / GQA).

Online-softmax tiling (Dao et al.) adapted to the TPU memory hierarchy:
  - grid (B·H, n_q, n_kv): the two trailing grid dims iterate sequentially on
    a core, so f32 scratch accumulators (m, l, acc) persist across the kv
    sweep of one q block — the TPU analogue of a CUDA thread-block's SRAM
    state;
  - block shapes (block_q × head_dim) / (block_k × head_dim) are multiples of
    (8, 128) so QK^T and PV land on the MXU at full tile occupancy;
  - VMEM working set per step: q + k + v + acc ≈ (bq + 2·bk)·hd·2B + bq·hd·4B
    ≈ 0.4 MiB at bq=bk=512, hd=128 — comfortably inside ~16 MiB VMEM, leaving
    headroom for double-buffered DMA of the next kv block;
  - GQA maps grid head h to kv head h // group_size in the k/v index_map —
    kv blocks are fetched once per q-head group member but never materialized
    at H width;
  - causal + window masks are applied per tile; fully-masked kv blocks are
    skipped via @pl.when (for causal this halves the sweep; for SWA it makes
    the sweep O(window) instead of O(S)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, block_q, block_k, n_kv_blocks, causal, window, scale,
):
    _, qi, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # tile-level skip: block is live unless fully masked
    live = True
    if causal:
        live = (kj * block_k) <= (qi * block_q + block_q - 1)
    if window is not None:
        live_w = (kj * block_k + block_k - 1) > (qi * block_q - window)
        live = jnp.logical_and(live, live_w) if causal else live_w

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)  # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]  # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (bq, bk)
        corr = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)  # (bk, hd)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(kj == n_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention_pallas(
    q: jnp.ndarray,  # (B, S, H, hd) — pre-RoPE'd
    k: jnp.ndarray,  # (B, S, K, hd)
    v: jnp.ndarray,  # (B, S, K, hd)
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    n_q, n_k = S // block_q, S // block_k
    scale = hd**-0.5

    qh = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(B * K, S, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(B * K, S, hd)

    kernel = functools.partial(
        _flash_kernel,
        block_q=block_q, block_k=block_k, n_kv_blocks=n_k,
        causal=causal, window=window, scale=scale,
    )
    hints = {}
    if not interpret:
        from jax.experimental.pallas import tpu as pltpu

        hints = {
            # head and q-block axes are independent; the kv axis carries the
            # online-softmax running state (m/l/acc scratch)
            "compiler_params": pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            "cost_estimate": pl.CostEstimate(
                # 2 matmuls of (S, hd)x(hd, S) per head + the rescale traffic
                flops=4 * B * H * S * S * hd,
                bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize
                + q.size * q.dtype.itemsize,
                transcendentals=B * H * S * S,  # exp in the online softmax
            ),
        }
    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda h, i, j, G=G: (h // G, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda h, i, j, G=G: (h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            _vmem_scratch(block_q, 1),
            _vmem_scratch(block_q, 1),
            _vmem_scratch(block_q, hd),
        ],
        **hints,
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


def _vmem_scratch(r: int, c: int):
    """f32 VMEM scratch (r, c); pltpu.VMEM works on TPU and in interpret mode."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM((r, c), jnp.float32)
