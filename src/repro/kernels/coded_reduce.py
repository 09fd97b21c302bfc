"""Pallas TPU kernel: coded gradient reduction (encode/decode hot-spot).

The paper's per-worker encode is ``g̃ = Σ_p w[p] · g[p]`` over n_i partial
gradient buffers, and the master-side decode is the same shape over coded
gradients — a (P,)·(P, D) weighted row reduction.  Done naively (sequential
axpy) the (P, D) stack is read once but the (D,) accumulator is re-read and
re-written P times from HBM; the kernel is a single VMEM-tiled pass that
touches every gradient byte exactly once:

    HBM bytes:  axpy  ≈ P·D·4 reads + 2·(P−1)·D·4 accumulator traffic
                kernel = P·D·itemsize reads + D·4 writes

Both schedules are memory-bound (arithmetic intensity ≈ 0.5 flop/byte), so
the byte ratio IS the speedup bound: ≈ (3P−2)/(P+1) ≈ 2.7× at P=8 over an
axpy whose accumulator misses cache, and ≥ 1.0× against XLA's best fusion of
the same loop (measured on every host by ``benchmarks/kernels_bench.py``,
which gates ``coded_reduce`` fused ≥ 1.0× the axpy loop — numbers live in
``results/BENCH_run.json``, accounting in DESIGN.md §12; the 2019-era claim
that this file's kernel was unconditionally fastest predated that gate).

Structure (the multi-stage tiling the wire kernels in ``wire.py`` share):

  - 2-D grid ``(n_d, n_p)`` over (D-tiles × P-chunks).  The P-chunk axis is
    the trailing (fastest, sequential) grid dim, so the f32 VMEM accumulator
    scratch persists across one D-tile's chunk sweep — flash-attention's
    scratch idiom (see ``flash_attention.py``).  The D axis is declared
    ``parallel`` in ``dimension_semantics`` (tiles are independent), the P
    axis ``arbitrary`` (carries the accumulator).
  - Block shapes: g ``(PC, T)`` VMEM, w ``(PC, 1)`` VMEM (broadcast against
    the lane dim), out ``(1, T)``; T = ``TILE_D`` = 512 lanes keeps the
    working set PC·T·4B ≤ 256 KiB — far under VMEM, long enough to amortize
    the HBM→VMEM DMA.  On TPU, ``tile_d`` is autotuned over {512, 1024,
    2048} (``autotune.best_tile_d``); elsewhere the default stands.
  - The last D tile is handled IN KERNEL: no ``jnp.pad`` (the old full-array
    pad copy doubled peak HBM for the encode input — regression-tested
    structurally in tests/test_wire_kernels.py: no ``pad`` primitive in the
    non-interpret trace; interpret-mode ``memory_analysis`` is dominated by
    the interpreter's own copies and cannot see the win).  OOB lanes of
    the final tile read garbage (NaN in interpret mode) but every op here is
    lane-local, so the garbage stays in lanes the final block write-back
    drops.  A ragged last P-chunk (P % PC ≠ 0) IS masked, because the chunk
    reduction crosses rows: ``jnp.where`` on the product, not a multiply
    (0·NaN = NaN).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_D = 512  # default lane tile; TPU runs pick via autotune.best_tile_d
P_CHUNK = 128  # max sublane rows per grid step (g block ≤ 128·2048·4B = 1 MiB)


def _chunk_contrib(w, g, *, rows_live: int | None = None):
    """One P-chunk's contribution Σ_rows w·g, f32, lane-local.

    Shared by ``coded_reduce`` and the fused wire kernels in ``wire.py`` so
    their reduce stages accumulate in the SAME order — the bit-equality
    contract between the fused int8 encode kernel and the host composition
    oracle rests on this function being the only reduce implementation.

    ``rows_live``: number of in-bounds rows when the chunk overhangs P
    (garbage rows must be excluded with selects on BOTH operands —
    multiplying a garbage NaN by a 0 weight still yields NaN).

    The reduction is a (1, PC)·(PC, T) ``dot_general``, NOT a mul+sum: a
    visible mul feeding a sum accumulator is fair game for LLVM's
    shape-dependent FMA contraction, which compiles DIFFERENTLY in the two
    interpret-mode kernel programs that share this function and breaks the
    wire kernels' bit-equality contract at rare shapes.  A dot's
    accumulation order is fixed by the dot emitter's shape-determined
    tiling, so identical (PC, T) gives identical bits in every kernel.
    ``precision=HIGHEST`` pins a full-f32 contraction in Mosaic (its default
    may round the operands to bf16, which would cost the gradient and the
    int8 decode's ``a_w·scale_w`` weights mantissa bits); the interpreter
    computes f32 either way.
    """
    wf = w.astype(jnp.float32)  # (PC, 1)
    gf = g.astype(jnp.float32)  # (PC, T)
    if rows_live is not None:
        rmask = jax.lax.broadcasted_iota(jnp.int32, (wf.shape[0], 1), 0) < rows_live
        wf = jnp.where(rmask, wf, 0.0)
        gf = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, gf.shape, 0) < rows_live, gf, 0.0
        )
    return jax.lax.dot_general(
        wf, gf,
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (1, T)


def _coded_reduce_kernel(w_ref, g_ref, o_ref, acc_scr, *, n_p, rows_tail):
    # w_ref: (PC, 1), g_ref: (PC, T), o_ref: (1, T), acc_scr: (1, T) f32
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if rows_tail and n_p > 1:
        # ragged last chunk: mask only there, full chunks take the fast path
        @pl.when(p < n_p - 1)
        def _full():
            acc_scr[...] += _chunk_contrib(w_ref[...], g_ref[...])

        @pl.when(p == n_p - 1)
        def _tail():
            acc_scr[...] += _chunk_contrib(w_ref[...], g_ref[...], rows_live=rows_tail)
    else:
        acc_scr[...] += _chunk_contrib(
            w_ref[...], g_ref[...], rows_live=rows_tail or None
        )

    @pl.when(p == n_p - 1)
    def _emit():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def _grid_geom(P: int, D: int, tile_d: int) -> tuple[int, int, int, int]:
    """(n_d, n_p, chunk, rows_tail): D-tiles, P-chunks, rows per chunk and
    live rows of the ragged final chunk (0 when P divides evenly)."""
    chunk = min(P, P_CHUNK)
    n_p = -(-P // chunk)
    n_d = -(-D // tile_d)
    rows_tail = P - (n_p - 1) * chunk
    return n_d, n_p, chunk, 0 if rows_tail == chunk else rows_tail


def _tpu_call_hints(n_d: int, flops: int, nbytes: int, interpret: bool) -> dict:
    """dimension_semantics + CostEstimate kwargs (compiled TPU path only —
    the interpreter has no Mosaic scheduler to hint)."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        "cost_estimate": pl.CostEstimate(
            flops=flops, bytes_accessed=nbytes, transcendentals=0
        ),
    }


@functools.partial(
    jax.jit, static_argnames=("interpret", "tile_d", "out_dtype", "name")
)
def coded_reduce_pallas(
    g: jnp.ndarray,
    w: jnp.ndarray,
    *,
    interpret: bool = False,
    tile_d: int | None = None,
    out_dtype: jnp.dtype | None = None,
    name: str = "coded_reduce",
) -> jnp.ndarray:
    """g: (P, D) row stack; w: (P,) coefficients -> (D,) = Σ_p w[p]·g[p].

    ``g`` may be any dtype the VPU casts from (f32/bf16 gradients, int8 wire
    payloads — the int8 decode in ``wire.py`` is this kernel); accumulation
    is always f32.  ``out_dtype`` defaults to ``g.dtype`` (pass f32 when
    reducing an int8 wire).  ``tile_d`` overrides the lane tile (autotuned on
    TPU via :func:`repro.kernels.autotune.best_tile_d`).  No padding copy is
    made at any D (DESIGN.md §12).  ``name`` is the kernel's name in a
    profiler trace.
    """
    P, D = g.shape
    td = int(tile_d) if tile_d else TILE_D
    odt = out_dtype if out_dtype is not None else g.dtype
    n_d, n_p, chunk, rows_tail = _grid_geom(P, D, td)
    from jax.experimental.pallas import tpu as pltpu

    out = pl.pallas_call(
        functools.partial(_coded_reduce_kernel, n_p=n_p, rows_tail=rows_tail),
        name=name,
        grid=(n_d, n_p),
        in_specs=[
            pl.BlockSpec((chunk, 1), lambda i, p: (p, 0)),
            pl.BlockSpec((chunk, td), lambda i, p: (p, i)),
        ],
        out_specs=pl.BlockSpec((1, td), lambda i, p: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, D), odt),
        scratch_shapes=[pltpu.VMEM((1, td), jnp.float32)],
        **_tpu_call_hints(
            n_d,
            flops=2 * P * D,
            nbytes=P * D * g.dtype.itemsize + D * jnp.dtype(odt).itemsize,
            interpret=interpret,
        ),
        interpret=interpret,
    )(w.reshape(P, 1), g)
    return out[0]
