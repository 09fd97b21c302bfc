"""Pallas TPU kernels for the int8 wire format: fused encode, int8 decode.

The spmd wire path (``core/aggregator.faithful_spmd_step``) compresses each
worker's coded gradient to int8 with error feedback.  Composed from separate
ops the fp32 wire tensor makes a full HBM round trip between the reduce and
the quantize:

    unfused:  reduce → HBM (D,) f32 → read → +err → max → quantize → q, err'
    fused  :  one kernel emits (q int8, scale, new_err) — the fp32 coded
              tensor lives only tile-by-tile in VMEM, never in HBM.

The global quantization scale needs max|coded| over all of D before the
first int8 byte can be written, so the kernel is **two-phase**: a leading
grid axis sweeps the whole (D-tiles × P-chunks) space twice.  Phase 0
accumulates each tile's coded values (reduce + error feedback) and folds
their |·| into a running-max scratch; phase 1 recomputes the tile (g is read
twice from HBM — cheaper than writing+reading a (D,) f32 wire, see the
byte accounting in DESIGN.md §12) and emits the quantized tile, the scale
and the new error-feedback tile.  Scratch persists across the whole grid
(all axes ``arbitrary`` — the phase boundary is a real dependency).

Bit-equality contract (interpret mode): phase arithmetic uses the SAME
``_chunk_contrib`` accumulation as ``coded_reduce_pallas`` and the same
elementwise quantize formulas as the host definition (``ref.quantize_int8``
/ ``ref.dequantize``), and f32 ``max`` is exactly commutative/associative,
so the kernel's (q, scale, new_err) is **bit-equal** to
``ref.encode_int8_oracle_np`` — strict per-op IEEE f32 numpy for
reduce/+err/quantize, and the correctly-rounded EXACT residual for
``new_err`` (the fused multiply-subtract this kernel compiles to rounds
``coded − q·scale`` once; the oracle computes the same value through exact
f64 arithmetic rather than trusting a compiler's FMA choice, which is
shape-dependent for jitted jnp compositions).  Two more compiler
discretions are designed out rather than hoped away: the chunk reduction
is a ``dot_general`` (a visible mul feeding a sum accumulator compiles
with different FMA contraction in different kernel programs — see
``_chunk_contrib``), and the scale is a MULTIPLY by the f32 constant
``INV_127`` (XLA rewrites division by a literal into a non-IEEE reciprocal
multiply; ``coded / scale`` with its runtime divisor stays true division).
Pinned across shapes/dtypes and multi-step error-feedback chains in
tests/test_wire_kernels.py.

Decode consumes the wire directly: the int8 payloads stacked (m, D) reduce
under per-worker weights a_w·scale_w in ONE pass of the same tiled kernel —
dequantization is the weight multiply, the f32 dequantized tensors never
materialize.  Reading int8 moves 4× fewer bytes than an fp32 wire.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.coded_reduce import TILE_D, _chunk_contrib, _grid_geom

EPS_SCALE = 1e-12  # quantize floor: scale = max(max|coded|, EPS_SCALE)·(1/127)
# the wire format defines scale as a MULTIPLY by the f32 constant 1/127, not
# a division by 127: XLA rewrites division-by-constant into a reciprocal
# multiply that is NOT correctly-rounded IEEE division, so `mx / 127.0`
# would be irreproducible in the strict-numpy oracle (observed 1-ulp scale
# mismatches).  An IEEE f32 multiply by an agreed constant is exact to
# reproduce anywhere.  The elementwise `coded / scale` below has a RUNTIME
# divisor, which XLA cannot rewrite — that one is true IEEE division.
INV_127 = 1.0 / 127.0


def _encode_kernel(
    w_ref, g_ref, err_ref, q_ref, scale_ref, err_out_ref, acc_scr, mx_scr,
    *, n_d, n_p, rows_tail, d_total, tile_d,
):
    """Two-phase fused encode.  Grid (2, n_d, n_p): phase × D-tile × P-chunk.

    acc_scr (1, T) f32: the running coded tile (reduce stage).
    mx_scr  (1, 1) f32: running max|coded| across phase-0 tiles.
    """
    phase, j, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(jnp.logical_and(phase == 0, jnp.logical_and(j == 0, p == 0)))
    def _init_max():
        mx_scr[...] = jnp.zeros_like(mx_scr)

    @pl.when(p == 0)
    def _init_acc():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if rows_tail and n_p > 1:
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
    def _tile_done():
        # error feedback folds in at the tile level; out-of-bounds lanes of
        # the last tile hold garbage (NaN in interpret mode) which the
        # lane mask keeps out of the max (writes to them are dropped)
        coded = acc_scr[...] + err_ref[...].astype(jnp.float32)  # (1, T)
        lane = j * tile_d + jax.lax.broadcasted_iota(jnp.int32, coded.shape, 1)
        live = lane < d_total

        @pl.when(phase == 0)
        def _scan_max():
            mx_scr[...] = jnp.maximum(
                mx_scr[...], jnp.max(jnp.where(live, jnp.abs(coded), 0.0))
            )

        @pl.when(phase == 1)
        def _emit():
            scale = jnp.maximum(mx_scr[0, 0], EPS_SCALE) * jnp.float32(INV_127)
            q = jnp.clip(jnp.round(coded / scale), -127, 127).astype(jnp.int8)
            q_ref[...] = q
            # compiles to a fused multiply-subtract: new_err is the exact
            # residual rounded once (what encode_int8_oracle_np specifies)
            err_out_ref[...] = coded - q.astype(jnp.float32) * scale
            scale_ref[...] = jnp.full_like(scale_ref, scale)


@functools.partial(jax.jit, static_argnames=("interpret", "tile_d"))
def coded_encode_int8_pallas(
    g: jnp.ndarray,
    w: jnp.ndarray,
    err: jnp.ndarray,
    *,
    interpret: bool = False,
    tile_d: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused encode+quantize+error-feedback.

    g: (P, D) per-slot gradient stack; w: (P,) encode coefficients;
    err: (D,) f32 carried quantization residual.
    Returns ``(q (D,) int8, scale () f32, new_err (D,) f32)`` with
    ``dequantize(q, scale) + new_err == coded_reduce(g, w) + err`` exactly
    (the fp32 coded tensor never reaches HBM).
    """
    P, D = g.shape
    td = int(tile_d) if tile_d else TILE_D
    n_d, n_p, chunk, rows_tail = _grid_geom(P, D, td)
    from jax.experimental.pallas import tpu as pltpu

    hints = {}
    if not interpret:
        hints = {
            "compiler_params": pltpu.CompilerParams(
                # the phase axis carries the global max; every axis sequential
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            ),
            "cost_estimate": pl.CostEstimate(
                flops=2 * 2 * P * D + 6 * D,
                bytes_accessed=2 * (P * g.dtype.itemsize + 4) * D + 5 * D + 4,
                transcendentals=0,
            ),
        }
    q, scale, new_err = pl.pallas_call(
        functools.partial(
            _encode_kernel,
            n_d=n_d, n_p=n_p, rows_tail=rows_tail, d_total=D, tile_d=td,
        ),
        name="coded_encode_int8",
        grid=(2, n_d, n_p),
        in_specs=[
            pl.BlockSpec((chunk, 1), lambda ph, i, p: (p, 0)),
            pl.BlockSpec((chunk, td), lambda ph, i, p: (p, i)),
            pl.BlockSpec((1, td), lambda ph, i, p: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, td), lambda ph, i, p: (0, i)),
            pl.BlockSpec((1, 1), lambda ph, i, p: (0, 0)),
            pl.BlockSpec((1, td), lambda ph, i, p: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, D), jnp.int8),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, td), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        **hints,
        interpret=interpret,
    )(w.reshape(P, 1), g, err.reshape(1, D))
    return q[0], scale[0, 0], new_err[0]


def coded_decode_int8_pallas(
    q: jnp.ndarray,
    ws: jnp.ndarray,
    *,
    interpret: bool = False,
    tile_d: int | None = None,
) -> jnp.ndarray:
    """Decode straight off the int8 wire: Σ_w ws[w]·q[w] in one tiled pass.

    q: (m, D) int8 wire payloads; ws: (m,) per-worker a_w·scale_w (the
    dequantization IS the weight multiply).  Returns the decoded (D,) f32
    gradient; no per-worker f32 tensor is ever materialized.
    """
    from repro.kernels.coded_reduce import coded_reduce_pallas

    return coded_reduce_pallas(
        q, ws, interpret=interpret, tile_d=tile_d, out_dtype=jnp.float32,
        name="coded_decode_int8",
    )
