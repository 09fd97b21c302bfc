"""Coded gradient aggregation on a JAX SPMD mesh.

Three implementations of the same math, used at different layers:

1. ``protocol_reference`` — the paper's protocol verbatim in pure jnp: every
   worker materializes its coded gradient g̃_w = Σ_j B[w,j]·g_j (the tensor a
   real deployment puts on the wire), the master decodes g = Σ_w a_w·g̃_w.
   Oracle for tests and the convergence benchmarks.  O(m·n) backward passes.

2. ``fused_coded_value_and_grad`` — the fused form.  Linear encoding
   commutes with ∇, so worker w's coded gradient is ∇_θ Σ_j B[w,j]·L(D_j),
   ONE backward pass over a weighted loss; folding the decode coefficient
   a_w in as well, the ordinary data-parallel gradient psum that XLA inserts
   *is* the decode:  g = ∇_θ Σ_w a_w Σ_j B[w,j] L(D_j).   Coded DP training
   becomes example-weighted DP — fully pjit/GSPMD-compatible, multi-pod
   safe, zero extra collectives vs naive DP.  (Beyond-paper optimization;
   agreement with (1) is property-tested.)

3. ``faithful_spmd_step`` — the protocol under ``jax.shard_map``, manual over
   every mesh axis (Mosaic kernels cannot be partitioned automatically, so
   the Pallas encode/decode must run where no axis is left to GSPMD).
   Each worker flattens its per-slot gradients into one (D,) buffer
   (``wire_ravel``, lane-aligned — see there), encodes them
   in a single pass through the roofline-optimal ``coded_reduce`` Pallas
   kernel (``interpret=True`` off-TPU), optionally compresses the flat wire
   tensor (int8 + error feedback) exactly where the wire format would apply,
   then decodes with ONE scaled psum over the flat buffer — not a per-leaf
   tree walk.  The master-side unravel back to the param pytree happens once,
   outside the collective.  Used for protocol benchmarks and as the
   compression-enabled path.

The device-resident data-path contract (DESIGN.md §6) lives here too:
``slot_weights_device`` is the in-jit twin of the host ``slot_weights``;
``unique_batch_device`` sums those slot weights per partition and hands the
fused step the k·mb unique sequences, where the host ``_flat_batch`` oracle
replicates them over every slot.  Both consume the small per-step device
inputs (decode vector ``a`` (m,), ``support`` (m,k)) plus the plan tensors
that the engine keeps device-resident between rebalances.

Deployment note (see DESIGN.md §3): within one SPMD program all chips step in
lock-step, so the (s+1)× compute redundancy buys gradient *exactness when
any ≤s coded workers' contributions are masked out* (deadline-based
exclusion, pod preemption, link loss).  The wall-clock win appears when the
coding axis crosses an MPMD boundary — pods over DCN — which is exactly how
``coding_axes=("pod",)`` configures it; the timing model lives in
core/simulator.py.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.coding import CodingScheme
from repro.core.decoding import Decoder
from repro.kernels import ref as kref
from repro.kernels.coded_reduce import coded_reduce_pallas
from repro.kernels.wire import coded_decode_int8_pallas, coded_encode_int8_pallas

__all__ = [
    "CodedPlan",
    "make_plan",
    "slot_weights",
    "slot_weights_device",
    "support_slot_mask",
    "support_slot_mask_device",
    "unique_batch_device",
    "pack_coded_batch",
    "protocol_reference",
    "fused_coded_value_and_grad",
    "faithful_spmd_step",
    "wire_ravel",
    "wire_unraveler",
    "remap_err_rows",
]

PyTree = Any
LossFn = Callable[[PyTree, PyTree], jnp.ndarray]  # (params, slot_batch) -> scalar


LANE = 128  # TPU vector lane width: the minor-dim tile of every HBM layout


def _lane_pad(shape: tuple[int, ...]) -> int:
    """Zero columns that round a ≥2-D leaf's minor dim up to a lane multiple."""
    return (-shape[-1]) % LANE if len(shape) >= 2 else 0


def wire_ravel(tree: PyTree) -> jnp.ndarray:
    """Flatten a gradient pytree into the spmd wire's (D,) f32 buffer.

    Leaf order is ``jax.tree.leaves`` order, as in ``ravel_pytree``, but
    every ≥2-D leaf's minor dim is first zero-padded to a multiple of
    :data:`LANE`.  On TPU a leaf whose minor dim is not a lane multiple
    (d_model 960, kv width 320) is stored with padded tiles, and flattening
    it straight into a concatenation makes XLA's TPU backend emit a
    per-row relayout whose compile time grows with the row count — minutes
    for one full-width model.  Padded, the relayout is tile-aligned.  The
    pad columns carry zeros through the encode (so they never move the
    int8 scale) and :func:`wire_unraveler` drops them again."""
    parts = []
    for x in jax.tree.leaves(tree):
        x = x.astype(jnp.float32)
        pad = _lane_pad(x.shape)
        if pad:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
        parts.append(x.reshape(-1))
    return jnp.concatenate(parts)


def wire_unraveler(like: PyTree) -> tuple[Callable[[jnp.ndarray], PyTree], int]:
    """Inverse of :func:`wire_ravel` for trees shaped like ``like``, and the
    wire width D it expects."""
    leaves, treedef = jax.tree.flatten(like)
    layout, off = [], 0
    for x in leaves:
        shape = tuple(x.shape)
        padded = shape[:-1] + (shape[-1] + _lane_pad(shape),) if shape else shape
        n = int(np.prod(padded))
        layout.append((off, n, padded, shape, x.dtype))
        off += n

    def unravel(flat: jnp.ndarray) -> PyTree:
        out = []
        for start, n, padded, shape, dtype in layout:
            x = flat[start:start + n].reshape(padded)
            if padded != shape:
                x = x[..., : shape[-1]]
            out.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return unravel, off


def remap_err_rows(err: jnp.ndarray, old_of_new) -> jnp.ndarray:
    """Per-worker wire-state row remap for a membership transition
    (DESIGN.md §13).

    ``err`` is the spmd backend's (m_old, width) error-feedback buffer;
    ``old_of_new[i]`` is the old index that became new worker ``i``, or
    None for a joiner.  Retained workers keep their accumulated residual
    row — gathered ON DEVICE, so the old buffer is consumed without a host
    round-trip — while joiners (and the rows of departed workers) start
    from zero.  Departed state must not leak: a leaver's residual encodes
    coefficients that no longer exist in the remapped B."""
    err = jnp.asarray(err)
    m_old = int(err.shape[0])
    idx = np.array([m_old if o is None else int(o) for o in old_of_new], np.int32)
    if np.any((idx < 0) | (idx > m_old)):
        raise ValueError(f"row map {list(old_of_new)} out of range for m_old={m_old}")
    padded = jnp.concatenate([err, jnp.zeros((1,) + err.shape[1:], err.dtype)], axis=0)
    return jnp.take(padded, jnp.asarray(idx), axis=0)


@dataclasses.dataclass(frozen=True)
class CodedPlan:
    """Device-feedable view of a CodingScheme.

    Attributes:
      slot_pids: (m, n_max) int32 partition id per worker slot (0-padded).
      slot_mask: (m, n_max) float, 1 for real slots, 0 for padding.
      slot_coeff: (m, n_max) float32, B[w, slot_pids[w, s]] (0 on padding).
      m, k, n_max: sizes.
    """

    slot_pids: np.ndarray
    slot_mask: np.ndarray
    slot_coeff: np.ndarray
    m: int
    k: int
    n_max: int


def make_plan(scheme: CodingScheme, n_slots: int | None = None) -> CodedPlan:
    """``n_slots`` pads every worker to a fixed slot count so elastic
    re-encodes (new c estimates -> new allocation) never change array shapes
    and therefore never trigger recompilation."""
    m, k = scheme.m, scheme.k
    n_max = max(1, max(scheme.allocation.counts))
    if n_slots is not None:
        if n_slots < n_max:
            raise ValueError(f"n_slots={n_slots} < allocation max {n_max}")
        n_max = n_slots
    pids = np.zeros((m, n_max), dtype=np.int32)
    mask = np.zeros((m, n_max), dtype=np.float32)
    coeff = np.zeros((m, n_max), dtype=np.float32)
    for w, parts in enumerate(scheme.allocation.partitions):
        for slot, j in enumerate(parts):
            pids[w, slot] = j
            mask[w, slot] = 1.0
            coeff[w, slot] = scheme.B[w, j]
    return CodedPlan(slot_pids=pids, slot_mask=mask, slot_coeff=coeff, m=m, k=k, n_max=n_max)


def support_slot_mask(plan: CodedPlan, support: np.ndarray) -> np.ndarray:
    """Slot-space view of an (m, k) partial-work completion mask: 1 where
    the worker finished that slot's partition, re-masked by ``slot_mask``
    because padding slots gather pid 0.  The single place the padding
    invariant is encoded — used by the fused weights AND the spmd coeffs."""
    done = np.asarray(support, np.float32)[np.arange(plan.m)[:, None], plan.slot_pids]
    return done * plan.slot_mask


def slot_weights(
    plan: CodedPlan, decode_vec: np.ndarray, support: np.ndarray | None = None
) -> np.ndarray:
    """Fused-path weights: W[w,s] = a_w · B[w, pid(w,s)] / k  (0 on padding).

    Σ_{w,s} W[w,s]·L_{pid(w,s)} = (1/k)·Σ_j (a·B)_j·L_j = mean partition loss,
    so its gradient is the decoded mean gradient.

    ``support`` is the optional (m, k) partial-work completion mask (see
    :class:`~repro.core.decoding.DecodeOutcome`): slots whose partition a
    worker did not finish get weight 0, so the fused/spmd paths differentiate
    exactly the work that exists — the inexact-decode contract.
    """
    a = np.asarray(decode_vec, dtype=np.float32).reshape(plan.m, 1)
    w = a * plan.slot_coeff * plan.slot_mask / plan.k
    if support is not None:
        w = w * support_slot_mask(plan, support)
    return w.astype(np.float32)


def uniform_weights(plan: CodedPlan) -> np.ndarray:
    """Uncoded-DP weights (naive scheme): every real slot weight 1/k."""
    return (plan.slot_mask / plan.k).astype(np.float32)


# ---------------------------------------------------------------------------
# device-resident twins of the host pack/weights (run INSIDE the jitted step)
# ---------------------------------------------------------------------------


def support_slot_mask_device(
    support: jnp.ndarray, slot_pids: jnp.ndarray, slot_mask: jnp.ndarray
) -> jnp.ndarray:
    """In-jit :func:`support_slot_mask`: gather the (m, k) completion mask
    into slot space, re-masked by ``slot_mask`` because padding slots gather
    pid 0 — the device-side home of the padding invariant (used by the fused
    weights AND the spmd wire coefficients)."""
    done = jnp.take_along_axis(support.astype(jnp.float32), slot_pids, axis=1)
    return done * slot_mask


def slot_weights_device(
    a: jnp.ndarray,
    support: jnp.ndarray,
    slot_coeff: jnp.ndarray,
    slot_mask: jnp.ndarray,
    slot_pids: jnp.ndarray,
    k: int,
) -> jnp.ndarray:
    """In-jit :func:`slot_weights`: W[w,s] = a_w·B[w,pid]·done[w,pid]/k.

    ``a`` (m,) and ``support`` (m, k) are the only per-step device inputs;
    ``slot_coeff`` / ``slot_mask`` / ``slot_pids`` are the plan tensors the
    engine keeps device-resident between rebalances.  Callers without
    partial work pass an all-ones ``support`` — `done·mask == mask` then,
    so the exact path is bit-identical to the host formula.
    """
    done = support_slot_mask_device(support, slot_pids, slot_mask)
    w = a.astype(jnp.float32)[:, None] * slot_coeff * done / k
    return w.astype(jnp.float32)


def unique_batch_device(
    partition_batch: dict, slot_pids: jnp.ndarray, weights: jnp.ndarray, k: int
) -> dict:
    """In-jit encode: partition-major leaves (k, mb, ...) -> the k·mb unique
    sequences (k·mb, ...) with per-sequence loss weights c_j/mb.

    By linearity Σ_{w,s} W[w,s]·L_{pid(w,s)} = Σ_j c_j·L_j, so one
    forward/backward over the unique batch gives the decoded gradient of
    the (s+1)×-replicated slot batch — exactly, for exact, inexact and
    partial-work decodes, wherever the loss is per sequence.  Inside one
    program the replicas move in lock step and tolerate nothing, so the
    fused step computes each partition once (DESIGN.md §3, §6).  No gather:
    the leaves are reshaped.  ``weights`` is the (m, n_slots) output of
    :func:`slot_weights_device`; padding and unfinished slots carry 0.
    """
    # c_j = Σ_{slots holding j} W[w,s] as an f32 segment-sum (a scatter-add),
    # never a one-hot matmul, whose TPU default precision is bf16: the
    # decode's cancellations happen here, before any bf16 op sees the weight
    c = jax.ops.segment_sum(
        weights.reshape(-1).astype(jnp.float32), slot_pids.reshape(-1), num_segments=k
    )
    out = {}
    for key, x in partition_batch.items():
        mb = x.shape[1]
        out[key] = x.reshape((k * mb,) + x.shape[2:])  # raises unless x holds k partitions
    out["weight"] = jnp.repeat(c, mb) / mb
    return out


def pack_coded_batch(
    partition_batch: PyTree, plan: CodedPlan, idx: jnp.ndarray | None = None
) -> PyTree:
    """Gather partition-major data (k, mb, ...) into slot-major (m, n_max, mb, ...).

    Replication factor is s+1 by construction — this materializes the coded
    working set, which is inherent to gradient coding.  Pass ``idx`` (the
    flattened (m·n_max,) slot_pids as a device array) to reuse a cached
    device copy instead of re-uploading the plan's; the gather runs on a
    2-D (k, mb·rest) view, which XLA lowers to straight row memcpys.
    """
    if idx is None:
        idx = jnp.asarray(plan.slot_pids.reshape(-1))  # (m*n_max,)

    def gather(x):
        out = jnp.take(x.reshape((x.shape[0], -1)), idx, axis=0)
        return out.reshape((plan.m, plan.n_max) + x.shape[1:])

    return jax.tree.map(gather, partition_batch)


# ---------------------------------------------------------------------------
# 1. protocol oracle (paper-verbatim)
# ---------------------------------------------------------------------------


def protocol_reference(
    loss_fn: LossFn,
    params: PyTree,
    partition_batch: PyTree,
    scheme: CodingScheme,
    available: Sequence[int] | None = None,
    decode_vec: np.ndarray | None = None,
    support: np.ndarray | None = None,
    grad_fn: Callable | None = None,
) -> tuple[PyTree, list[PyTree]]:
    """Paper protocol, literally.  Returns (decoded mean gradient, [g̃_w]).

    Workers compute per-partition gradients, encode with their B row, the
    master decodes from the available set.  Not jitted end-to-end (python
    loops) — this is the oracle, not the fast path.  Pass ``decode_vec`` to
    reuse a decode solved elsewhere (e.g. a GradientCode's fast path) and
    ``support`` (m, k completion mask) for partial-work iterations: worker w
    encodes only the partitions it finished, g̃_w = Σ_j B[w,j]·mask[w,j]·g_j.
    ``grad_fn`` lets long-lived callers (StepEngine) pass in a jitted
    ``jax.grad(loss_fn)`` built once, instead of re-tracing it per call.
    """
    m, k = scheme.m, scheme.k
    if grad_fn is None:
        grad_fn = jax.jit(jax.grad(loss_fn))
    part_grads = [
        grad_fn(params, jax.tree.map(lambda x, j=j: x[j], partition_batch)) for j in range(k)
    ]
    coded = []
    for w in range(m):
        gw = jax.tree.map(jnp.zeros_like, params)
        for j in scheme.allocation.partitions[w]:
            bwj = float(scheme.B[w, j]) * (1.0 if support is None else float(support[w, j]))
            gw = jax.tree.map(lambda acc, g, b=bwj: acc + b * g, gw, part_grads[j])
        coded.append(gw)
    if decode_vec is not None:
        a = np.asarray(decode_vec, np.float64)
        avail = [i for i in range(m) if abs(a[i]) > 1e-12]
    else:
        avail = list(range(m)) if available is None else list(available)
        a = Decoder(scheme).decode_vector(avail)
    decoded = jax.tree.map(jnp.zeros_like, params)
    for w in avail:
        if abs(a[w]) < 1e-12:
            continue
        decoded = jax.tree.map(lambda acc, g, aw=float(a[w]): acc + aw * g, decoded, coded[w])
    decoded = jax.tree.map(lambda g: g / k, decoded)
    return decoded, coded


# ---------------------------------------------------------------------------
# 2. fused production path (pjit-native)
# ---------------------------------------------------------------------------


def fused_coded_value_and_grad(loss_fn: LossFn) -> Callable[[PyTree, PyTree, jnp.ndarray], tuple]:
    """Returns f(params, slot_batch, weights) -> (weighted_loss, grads).

    slot_batch leaves: (m, n_max, mb, ...); weights: (m, n_max) from
    ``slot_weights``.  Shard slot axis 0 over the coding axes and XLA's DP
    gradient reduction performs the decode.
    """

    def weighted_loss(params: PyTree, slot_batch: PyTree, weights: jnp.ndarray) -> jnp.ndarray:
        flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), slot_batch)
        losses = jax.vmap(loss_fn, in_axes=(None, 0))(params, flat)  # (m*n_max,)
        return jnp.sum(losses * weights.reshape(-1).astype(losses.dtype))

    return jax.value_and_grad(weighted_loss)


# ---------------------------------------------------------------------------
# 3. faithful SPMD protocol (shard_map, manual over every mesh axis)
# ---------------------------------------------------------------------------


# wire-format definition lives with the kernel oracles; these aliases keep
# the historical names used throughout this module and its tests
_quantize_int8 = kref.quantize_int8
_dequantize = kref.dequantize


def faithful_spmd_step(
    loss_fn: LossFn,
    mesh: jax.sharding.Mesh,
    coding_axes: tuple[str, ...] = ("data",),
    compress: bool = False,
    interpret: bool | None = None,
    wire_kernel: bool | None = None,
) -> Callable:
    """Paper protocol under shard_map: flat Pallas encode, one-psum decode.

    The returned function f(params, slot_batch, coeff, a, err) ->
    (flat_grads, err') expects leaves of slot_batch shaped (m, n_max, mb, ...)
    sharded over the coding axes on dim 0; coeff = effective B coefficients
    (m, n_max) (slot mask — and any partial-work support mask — already folded
    in); a = decode vector scaled by 1/k, shape (m,); err = per-worker flat
    error-feedback buffer (m, D) when ``compress`` else (m, 1) (each coded
    worker keeps its own quantization residual on the wire tensor).

    Data path per worker: the per-slot gradient pytrees are flattened into a
    (n_max, D) stack (:func:`wire_ravel`, fixed leaf order), the encode
    g̃_w = Σ_s coeff[w,s]·g_s is ONE single-pass ``coded_reduce`` Pallas call
    (``interpret=True`` off-TPU — taken from the mesh's platform when
    ``interpret`` is None),
    and the master decode g = Σ_w a_w·g̃_w is ONE psum over the flat (D,)
    buffer instead of a per-leaf tree walk.  Callers unravel the result once
    with :func:`wire_unraveler` of the params structure.

    ``wire_kernel`` (``compress`` only) switches the quantize stage to the
    fused Pallas wire kernels (DESIGN.md §12): encode+quantize+error-feedback
    in ONE kernel — the fp32 wire tensor never materializes in HBM — and the
    decode consumes the int8 wire directly: ``all_gather`` of the (D,) int8
    payloads (4× fewer collective bytes than an fp32 psum) plus the gathered
    per-worker ``a_w·scale_w`` weights, reduced locally by the tiled int8
    kernel.  Replicated-decode semantics are identical to the psum up to
    f32 reduction order.  None → :func:`repro.kernels.autotune.
    wire_kernel_default` (True only where the fused kernel measured faster).

    Manual over EVERY mesh axis: Mosaic refuses to partition a Pallas call
    automatically, so no axis may be left to GSPMD around the kernels.
    Params enter replicated; on a mesh with a 'model' axis of size > 1 each
    model-axis shard computes its worker's gradient whole.
    """
    if interpret is None:
        interpret = mesh.devices.flat[0].platform != "tpu"
    if wire_kernel is None:
        from repro.kernels.autotune import wire_kernel_default

        wire_kernel = compress and wire_kernel_default()

    def worker_fn(params, slot_batch, coeff, a, err):
        # block shapes: slot_batch (1, n_max, mb, ...), coeff (1, n_max),
        # a (1,), err (1, D) or (1, 1)
        sb = jax.tree.map(lambda x: x[0], slot_batch)
        cw = coeff[0]  # (n_max,)

        def slot_grad(carry, slot):
            g = jax.grad(loss_fn)(params, slot)
            return carry, wire_ravel(g)

        _, gstack = jax.lax.scan(slot_grad, None, sb)  # (n_max, D)
        if compress and wire_kernel:
            # fused wire path: one kernel encodes straight to the int8 wire
            q, scale, new_err = coded_encode_int8_pallas(
                gstack, cw, err[0], interpret=interpret
            )
            new_err = new_err[None]
            q_all = jax.lax.all_gather(q, coding_axes, tiled=False)  # (W, D) i8
            ws_all = jax.lax.all_gather(scale * a[0], coding_axes)  # (W,)
            decoded = coded_decode_int8_pallas(q_all, ws_all, interpret=interpret)
            return decoded, new_err
        coded = coded_reduce_pallas(gstack, cw, interpret=interpret)  # (D,)
        if compress:
            # wire-format emulation: the flat g̃_w is what travels, so the
            # int8 quantization + error feedback applies to it wholesale
            coded = coded + err[0]
            deq = _dequantize(*_quantize_int8(coded))
            new_err = (coded - deq)[None]
            coded = deq
        else:
            new_err = err
        decoded = jax.lax.psum(coded * a[0], coding_axes)
        return decoded, new_err

    dp = jax.sharding.PartitionSpec(coding_axes)
    rep = jax.sharding.PartitionSpec()
    return jax.shard_map(
        worker_fn, mesh=mesh, in_specs=(rep, dp, dp, dp, dp), out_specs=(rep, dp),
        check_vma=False,
    )
