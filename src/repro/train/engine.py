"""StepEngine: the coded training step behind one of three interchangeable
gradient backends (DESIGN.md §3), on a fully device-resident data path
(DESIGN.md §6).

  - ``fused``     — production path.  Encode/decode folded into per-sequence
                    loss weights; ONE jitted fwd/bwd + AdamW with donated
                    buffers; XLA's DP reduction *is* the decode.  The slot
                    weights are computed INSIDE the jit from small per-step
                    device inputs and summed per partition, and forward/
                    backward run over the k·mb unique sequences only: inside
                    one program the (s+1)× replicas would move in lock step
                    and tolerate nothing, so no slot is replicated.
  - ``reference`` — the paper's protocol verbatim (O(m·n) backward passes,
                    python loops).  Oracle for tests/debugging; applies the
                    same AdamW update so whole-run comparisons work.
  - ``spmd``      — the faithful shard_map protocol on a mesh: per-worker
                    flat-gradient encode through the ``coded_reduce`` Pallas
                    kernel, optional int8 wire compression, single flat-psum
                    decode.  For protocol benchmarks and compression runs.

All backends consume the same inputs — partition-major host batch + decode
vector OR :class:`~repro.core.decoding.DecodeOutcome` from the
:class:`~repro.core.codec.Codec` — and produce the same decoded gradient
(property-tested across every registered scheme, exact and inexact), so
swapping the execution backend is a constructor argument, not a code
change.  An outcome's partial-work ``support`` mask zeroes unfinished
partitions identically in every backend: fused/spmd via slot weights,
reference via masked B rows.

Device residency contract: the plan tensors (``slot_pids`` / ``slot_coeff``
/ ``slot_mask``) are uploaded once per plan *object* and cached on device;
every value-changing path (elastic rebalance, checkpoint restore) rebuilds
the plan, so the next step re-uploads — nothing else ever re-materializes
them.  ``host_pack=True`` preserves the
pre-§6 host-side numpy pack over the replicated slot batch (oracle for
equivalence tests and the ``benchmarks/steptime.py`` before/after
comparison).  Replication lives only there and in the ``spmd`` backend,
whose workers are separate programs that each compute their slots.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.placement import place_rows
from repro.configs.base import TrainConfig
from repro.core.aggregator import (
    faithful_spmd_step,
    pack_coded_batch,
    protocol_reference,
    slot_weights,
    slot_weights_device,
    support_slot_mask_device,
    unique_batch_device,
    wire_unraveler,
)
from repro.core.codec import Codec
from repro.core.decoding import DecodeOutcome
from repro.launch.mesh import coded_axis_size, mesh_devices_for_m, remesh_for_m
from repro.obs.trace import NULL_TRACER
from repro.optim.adam import AdamWState, adamw_init, adamw_update, global_norm
from repro.optim.schedules import cosine_warmup

PyTree = Any

BACKENDS = ("reference", "fused", "spmd")

__all__ = ["BACKENDS", "TrainerState", "StepEngine", "EngineRebuild"]


@dataclasses.dataclass(frozen=True)
class EngineRebuild:
    """Report of one elastic spmd rebuild (DESIGN.md §13) — what was torn
    down, what was carried.  ``err_rows_carried`` counts retained workers
    whose int8 error-feedback residual survived the transition on device;
    params/optimizer state never appear here because the rebuild does not
    touch them at all (they stay on their devices and the re-jitted step
    consumes them via donation, exactly as before the transition)."""

    version: int  # Codec.version the engine is now keyed to
    m_before: int
    m_after: int
    mesh_rebuilt: bool  # coded-axis extent moved -> new mesh derived
    program_rebuilt: bool  # (m, n_slots) moved -> shard_map + pack re-jitted
    err_rows_carried: int
    err_rows_zeroed: int
    ms: float  # host-side rebuild latency (excludes lazy retrace)


@dataclasses.dataclass
class TrainerState:
    params: PyTree
    opt: AdamWState
    step: int


class StepEngine:
    """Jitted coded train step over a model + codec, backend-selectable.

    ``model`` must expose ``init(rng) -> params`` and
    ``weighted_loss(params, batch) -> scalar`` where ``batch["weight"]``
    holds per-sequence loss weights (the LM contract; tests use tiny
    duck-typed models).  Shapes fed to the jitted path are fixed by the
    codec's slot capacity, so elastic re-encodes never recompile — they only
    invalidate the engine's device-resident plan cache (one re-upload).
    """

    def __init__(
        self,
        model,
        train_cfg: TrainConfig,
        codec: Codec,
        *,
        backend: str = "fused",
        mesh: jax.sharding.Mesh | None = None,
        coding_axes: tuple[str, ...] = ("data",),
        compress: bool = False,
        host_pack: bool = False,
        wire_kernel: bool | None = None,
    ):
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; choose from {BACKENDS}")
        if backend == "spmd" and mesh is None:
            raise ValueError("backend='spmd' needs a mesh")
        self.model = model
        self.tc = train_cfg
        self.codec = codec
        self.backend = backend
        self.mesh = mesh
        self.coding_axes = coding_axes
        self.compress = compress
        self.host_pack = host_pack
        # fused int8 wire kernels (DESIGN.md §12): None defers to the host
        # probe — on only where the fused encode measured faster (TPU)
        if wire_kernel is None:
            from repro.kernels.autotune import wire_kernel_default

            wire_kernel = compress and wire_kernel_default()
        self.wire_kernel = bool(wire_kernel) and compress
        # observability seam (DESIGN.md §10): the trainer installs its
        # tracer here; standalone engines keep the zero-cost NULL singleton
        self.tracer = NULL_TRACER

        # built ONCE: re-creating value_and_grad/grad transforms per call
        # used to re-trace the whole model every step
        self._vg = jax.value_and_grad(model.weighted_loss)

        # device-resident plan cache, keyed by plan object IDENTITY: every
        # path that changes plan values (rebalance, checkpoint restore)
        # rebuilds the plan object, so identity can never go stale the way
        # an externally-restored version counter could (DESIGN.md §6)
        self._plan_ref = None
        self._dev_pids: jnp.ndarray | None = None  # (m, n_slots) int32
        self._dev_coeff: jnp.ndarray | None = None  # (m, n_slots) f32
        self._dev_mask: jnp.ndarray | None = None  # (m, n_slots) f32
        self._dev_coeff_mask: jnp.ndarray | None = None  # slot_coeff*slot_mask
        self._ones_support: jnp.ndarray | None = None  # (m, k) f32

        # elastic rebuild bookkeeping (DESIGN.md §13), kept on every backend
        # so membership hooks are safe regardless of backend: the composed
        # row identity map of transitions applied since the last rebuild,
        # and the worker-axis shape the live spmd jits were built at
        self._row_map: list[int | None] | None = None
        self._spmd_m: int | None = None
        self._spmd_nslots: int | None = None
        self.last_rebuild: EngineRebuild | None = None
        # set when a rebuild moved the mesh: caller-held state (params, opt)
        # is still committed to the OLD device set and must be re-placed
        # (device-to-device) before it meets new-mesh outputs in a jit
        self._state_mesh_stale = False

        self._fused_step = jax.jit(self._make_fused_step(), donate_argnums=(0, 1))
        self._fused_grads = jax.jit(self._make_fused_grads())
        if host_pack:
            self._fused_step_host = jax.jit(
                self._make_fused_step_host(), donate_argnums=(0, 1)
            )
            self._fused_grads_host = jax.jit(lambda p, batch: self._vg(p, batch)[1])
        if backend != "fused":
            self._loss_fwd = jax.jit(self._make_packed_loss())
            self._apply = jax.jit(self._make_apply(), donate_argnums=(0, 1))
        if backend == "reference":
            self._ref_grad = jax.jit(jax.grad(self._slot_loss))
        if backend == "spmd":
            self._coeff_support = jax.jit(
                lambda coeff, pids, mask, sup: coeff
                * support_slot_mask_device(sup, pids, mask)
            )
            self._err = None  # per-worker flat error feedback, built lazily
            self._err_version: int | None = None  # codec.version _err belongs to
            self._err_width: int | None = None  # D when compressed, else 1
            self._unravel = None  # flat (D,) -> params pytree, built lazily
            self._build_spmd_program()

    # -- state -------------------------------------------------------------

    def init_state(self, rng: jax.Array) -> TrainerState:
        params = self.model.init(rng)
        return TrainerState(params=params, opt=adamw_init(params), step=0)

    # -- loss adapters ------------------------------------------------------

    def _slot_loss(self, params: PyTree, micro_batch: PyTree) -> jnp.ndarray:
        """Unweighted mean loss over one partition micro-batch — the
        per-worker loss the protocol backends differentiate."""
        mb = jax.tree.leaves(micro_batch)[0].shape[0]
        w = jnp.full((mb,), 1.0 / mb, jnp.float32)
        return self.model.weighted_loss(params, {**micro_batch, "weight": w})

    @staticmethod
    def _split_decode(a) -> tuple[np.ndarray, np.ndarray | None]:
        """Normalize a decode argument: bare vector or DecodeOutcome ->
        (vector, partial-work support mask or None)."""
        if isinstance(a, DecodeOutcome):
            return a.a, a.support
        return a, None

    # -- device-resident plan views (DESIGN.md §6) --------------------------

    def _device_plan(self) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """(slot_pids, slot_coeff, slot_mask) as cached device arrays.

        Uploaded once per plan object; a rebalance (or checkpoint restore)
        rebuilds the plan, so the next step pays ONE (m, n_slots)-sized
        upload and the steady-state host→device traffic is just the unique
        batch + the (m,)/(m,k) decode inputs.
        """
        plan = self.codec.plan
        if self._plan_ref is not plan:
            self._dev_pids = jnp.asarray(plan.slot_pids)
            self._dev_coeff = jnp.asarray(plan.slot_coeff)
            self._dev_mask = jnp.asarray(plan.slot_mask)
            self._dev_coeff_mask = jnp.asarray(plan.slot_coeff * plan.slot_mask)
            self._plan_ref = plan
        return self._dev_pids, self._dev_coeff, self._dev_mask

    def _support_dev(self, support: np.ndarray | None) -> jnp.ndarray:
        """(m, k) completion mask as a device array; all-ones when the step
        has no partial work (same trace either way — no recompiles).  Keyed
        by shape: a membership change (m or structural k moved) rebuilds it
        instead of feeding the stale-sized mask into a retraced step."""
        if support is None:
            shape = (self.codec.m, self.codec.k)
            if self._ones_support is None or self._ones_support.shape != shape:
                self._ones_support = jnp.ones(shape, jnp.float32)
            return self._ones_support
        return jnp.asarray(np.asarray(support), jnp.float32)

    def _flat_batch(
        self, partition_batch: dict[str, np.ndarray], a: np.ndarray,
        support: np.ndarray | None = None,
    ) -> dict:
        """HOST-side pack oracle: partition-major (k, mb, ...) -> replicated
        flat coded batch (m·n_slots·mb, ...) with decode/encode folded into
        per-seq weights.  The pre-§6 data path — kept as the
        ``host_pack=True`` baseline the unique-batch encode is tested
        against."""
        plan = self.codec.plan
        idx = plan.slot_pids.reshape(-1)  # (m*n_slots,)
        out = {}
        mb = None
        for key, arr in partition_batch.items():
            arr = np.asarray(arr)
            g = arr[idx]  # (m*n_slots, mb, ...)
            mb = arr.shape[1]
            out[key] = g.reshape((-1,) + arr.shape[2:])
        w = slot_weights(plan, a, support)  # (m, n_slots), includes the 1/k
        out["weight"] = (np.repeat(w.reshape(-1), mb) / mb).astype(np.float32)
        return out

    # -- step functions -----------------------------------------------------

    def _lr(self, step):
        return cosine_warmup(
            step, base_lr=self.tc.lr, warmup_steps=self.tc.warmup_steps,
            total_steps=self.tc.total_steps,
        )

    def _adamw(self, params, grads, opt, step):
        """AdamW apply behind the non-finite payload guard (DESIGN.md §11):
        a corrupted coded sum (NaN/Inf anywhere in the decoded gradient —
        global_norm is finite iff every leaf is) must never touch params or
        optimizer moments.  The guard is in-jit (no recompiles, no extra
        readback): grads are zeroed and the update reverted via selects, so
        the finite path is bit-identical to the unguarded step and the
        caller detects the skip from the returned non-finite grad_norm.
        Its ops carry the ``adamw`` name scope (DESIGN.md §10)."""
        tc = self.tc
        with jax.named_scope("adamw"):
            lr = self._lr(step)
            gnorm = global_norm(grads)
            ok = jnp.isfinite(gnorm)
            grads = jax.tree.map(lambda g: jnp.where(ok, g, jnp.zeros_like(g)), grads)
            new_params, new_opt = adamw_update(
                params, grads, opt,
                lr=lr, beta1=tc.beta1, beta2=tc.beta2, eps=tc.eps,
                weight_decay=tc.weight_decay, grad_clip=tc.grad_clip,
            )
            new_params = jax.tree.map(lambda n, o: jnp.where(ok, n, o), new_params, params)
            new_opt = jax.tree.map(lambda n, o: jnp.where(ok, n, o), new_opt, opt)
        return new_params, new_opt, gnorm, lr

    def _device_batch(self, pbatch, a, support, pids, coeff, mask):
        """In-jit weights over the unique batch: the paper's encode (name
        scope ``coded_pack``, DESIGN.md §10).  Same decoded gradient as the
        replicated _flat_batch, one pass per partition."""
        with jax.named_scope("coded_pack"):
            w = slot_weights_device(
                jnp.asarray(a, jnp.float32), support, coeff, mask, pids, self.codec.k
            )
            return unique_batch_device(pbatch, pids, w, self.codec.k)

    def _make_fused_step(self):
        def step_fn(params, opt, pbatch, a, support, pids, coeff, mask, step):
            batch = self._device_batch(pbatch, a, support, pids, coeff, mask)
            loss, grads = self._vg(params, batch)
            params, opt, gnorm, lr = self._adamw(params, grads, opt, step)
            return params, opt, {"loss": loss, "grad_norm": gnorm, "lr": lr}

        return step_fn

    def _make_fused_step_host(self):
        """Host-pack variant: consumes the pre-replicated flat batch."""

        def step_fn(params, opt, batch, step):
            loss, grads = self._vg(params, batch)
            params, opt, gnorm, lr = self._adamw(params, grads, opt, step)
            return params, opt, {"loss": loss, "grad_norm": gnorm, "lr": lr}

        return step_fn

    def _make_fused_grads(self):
        def grads_fn(params, pbatch, a, support, pids, coeff, mask):
            batch = self._device_batch(pbatch, a, support, pids, coeff, mask)
            return self._vg(params, batch)[1]

        return grads_fn

    def _make_packed_loss(self):
        """Weighted loss over the unique batch at the decoded weights,
        encoded in-jit (the metric the non-fused backends report)."""

        def loss_fn(params, pbatch, a, support, pids, coeff, mask):
            batch = self._device_batch(pbatch, a, support, pids, coeff, mask)
            return self.model.weighted_loss(params, batch)

        return loss_fn

    def _make_apply(self):
        """Optimizer update for backends that produce grads out-of-line."""

        def apply_fn(params, opt, grads, step):
            params, opt, gnorm, lr = self._adamw(params, grads, opt, step)
            return params, opt, {"grad_norm": gnorm, "lr": lr}

        return apply_fn

    def reset_error_feedback(self) -> None:
        """Zero the spmd backend's per-worker error-feedback residuals.

        Called after a non-finite decode (a corrupt payload pollutes the
        residual of every worker in that step's psum) and harmless
        otherwise; membership changes already reset via the codec-version
        key in :meth:`_spmd_gradients`."""
        if self.backend == "spmd" and self._err is not None:
            self._err = jnp.zeros_like(self._err)

    # -- elastic spmd rebuild (DESIGN.md §13) -------------------------------

    def _build_spmd_program(self) -> None:
        """(Re)create the mesh-pinned jits: the shard_map wire program and
        the in-jit slot pack.  Keyed on (m, n_slots), NOT on input shapes:
        the pack jit closes over the plan's (m, n_slots) reshape at trace
        time, so a transition where the m·n_slots product happens to
        coincide would otherwise reuse a stale trace and silently mis-shape
        the slot stack."""
        self._spmd_grads = jax.jit(
            faithful_spmd_step(
                self._slot_loss, self.mesh, self.coding_axes,
                compress=self.compress, wire_kernel=self.wire_kernel,
            )
        )
        self._pack_slots = jax.jit(
            lambda pbatch, idx: pack_coded_batch(pbatch, self.codec.plan, idx=idx)
        )
        self._spmd_m = self.codec.m
        self._spmd_nslots = self.codec.n_slots

    def _ensure_spmd_program(self) -> tuple[bool, bool]:
        """Bring mesh + jits in line with the codec's current worker set.
        Returns (mesh_rebuilt, program_rebuilt)."""
        m = self.codec.m
        mesh_rebuilt = False
        if coded_axis_size(self.mesh, self.coding_axes) != m:
            self.mesh = remesh_for_m(self.mesh, self.coding_axes, m)
            mesh_rebuilt = True
        program_rebuilt = m != self._spmd_m or self.codec.n_slots != self._spmd_nslots
        if mesh_rebuilt or program_rebuilt:
            self._build_spmd_program()
            program_rebuilt = True
        return mesh_rebuilt, program_rebuilt

    def _replicate_on_mesh(self, tree: PyTree) -> PyTree:
        """Re-place a replicated pytree onto the engine's CURRENT mesh.
        Device-to-device (no host round-trip); a no-op for arrays already
        placed there — this is how params/opt survive a mesh rebuild
        without being reconstructed."""
        return jax.device_put(
            tree, jax.sharding.NamedSharding(self.mesh, jax.sharding.PartitionSpec())
        )

    def check_membership(self, m_new: int) -> None:
        """Feasibility gate for a membership transition, called BEFORE any
        control-plane state mutates (the ElasticController's
        ``pre_transition`` hook): the spmd rebuild needs one device per
        coded worker times the mesh's non-coding extent.  Vetoing here
        keeps the transition atomic — codec, estimator, and sim are all
        untouched when this raises."""
        if self.backend != "spmd":
            return
        needed = (
            int(m_new) if self.mesh is None
            else mesh_devices_for_m(self.mesh, self.coding_axes, int(m_new))
        )
        avail = len(jax.devices())
        if needed > avail:
            raise ValueError(
                f"spmd rebuild infeasible: m={m_new} needs {needed} devices "
                f"({needed // int(m_new)} per coded worker), only {avail} available"
            )

    def note_membership(self, old_of_new: Sequence[int | None]) -> None:
        """Record an applied membership transition's row identity map (the
        controller's ``on_transition`` hook).  Multiple transitions between
        steps compose into one map; the next :meth:`rebuild` consumes it to
        carry retained workers' error-feedback rows."""
        if self.backend != "spmd":
            return
        oon = [None if o is None else int(o) for o in old_of_new]
        prev = self._row_map
        self._row_map = oon if prev is None else [
            None if o is None else prev[o] for o in oon
        ]

    def rebuild(self) -> EngineRebuild | None:
        """Force the §13 elastic rebuild now if one is pending (normally it
        runs lazily on the next gradient step).  No-op on non-spmd backends
        and on an engine that has not stepped yet (nothing to carry — the
        first step builds fresh state at the live m anyway).  Returns the
        rebuild report, or None when nothing was pending."""
        if self.backend != "spmd" or self._unravel is None:
            return None
        if self._err is not None and self._err_version == self.codec.version:
            return None
        self._rebuild_spmd()
        return self.last_rebuild

    def _rebuild_spmd(self) -> None:
        """The elastic rebuild path, keyed by ``Codec.version``: re-derive
        the mesh at the new m, re-jit the shard_map program if the worker
        axis moved, and carry retained workers' error-feedback rows across
        the transition (device gather — the old buffer is consumed without
        a host round-trip) while joiners/leavers get zeroed rows.

        Params and optimizer state are NOT touched: they live outside the
        worker axis, stay on their devices, and the re-jitted step donates
        them exactly as before — the membership delta is the only state
        that moves.  A version bump with no recorded identity map at an
        unchanged worker count is a pure re-encode (rebalance): every
        worker kept its identity, so the whole buffer carries — the
        residual is the quantization error of gradients already applied,
        which is coefficient-independent.  Engines driven through an
        ElasticController always see membership identity maps via
        :meth:`note_membership`; a direct ``Codec.remap_members`` caller
        that skips the hook gets zeroed rows whenever m moved (shape
        mismatch) — the conservative fallback."""
        t0 = time.perf_counter()
        m = self.codec.m
        m_before = self._spmd_m if self._spmd_m is not None else m
        mesh_rebuilt, program_rebuilt = self._ensure_spmd_program()
        width = self._err_width
        # dim 0 split over the coding axes: one row on each worker's device
        err_sharding = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(self.coding_axes)
        )
        carried = 0
        if (
            self._err is not None
            and self._row_map is not None
            and len(self._row_map) == m
        ):
            self._err = place_rows(self._err, self._row_map)
            carried = sum(1 for o in self._row_map if o is not None)
        elif (
            self._err is not None
            and self._row_map is None
            and self._err.shape == (m, width)
        ):
            carried = m  # pure rebalance: identities unchanged, all rows carry
        else:
            # placed at birth: an unplaced (m, D) buffer would sit whole on
            # the first device
            self._err = jnp.zeros((m, width), jnp.float32, device=err_sharding)
        if mesh_rebuilt:
            # the carried rows are still committed to the OLD device set;
            # re-place them onto the new mesh (device-to-device gather —
            # the rows never bounce through the host) under the program's
            # err spec
            self._err = jax.device_put(self._err, err_sharding)
            self._state_mesh_stale = True
        self._row_map = None
        self._err_version = self.codec.version
        self.last_rebuild = EngineRebuild(
            version=int(self.codec.version),
            m_before=int(m_before), m_after=int(m),
            mesh_rebuilt=mesh_rebuilt, program_rebuilt=program_rebuilt,
            err_rows_carried=int(carried), err_rows_zeroed=int(m - carried),
            ms=(time.perf_counter() - t0) * 1e3,
        )
        if self.tracer.enabled:
            self.tracer.instant(
                "engine.rebuild", **dataclasses.asdict(self.last_rebuild)
            )

    def state_dict(self) -> dict:
        """JSON-able wire-path state beyond (params, opt): the spmd
        backend's per-worker error-feedback buffer keyed to its codec
        version.  Restoring it makes a mid-churn spmd resume bit-exact
        INCLUDING the compression residuals; other backends hold no device
        state outside (params, opt) and return {}."""
        if self.backend != "spmd" or self._err is None:
            return {}
        return {
            "err": np.asarray(self._err, np.float32).tolist(),
            "err_version": int(self._err_version),
            "err_width": int(self._err_width),
        }

    def load_state_dict(self, state: dict) -> None:
        """Restore wire-path state.  The codec must already be restored
        (the trainer orders codec → elastic → engine), so the mesh and
        program are rebuilt here against the restored worker set, and the
        err buffer lands on device through the same placement path the
        elastic rebuild uses (:func:`repro.checkpoint.placement.place_rows`).
        An empty dict (old checkpoint, or pre-first-step) resets to the
        lazy-build state."""
        if self.backend != "spmd":
            return
        self._row_map = None
        self._ensure_spmd_program()
        if not state:
            self._err = None
            self._err_version = None
            return
        err = np.asarray(state["err"], np.float32)
        self._err = place_rows(err)
        self._err_version = int(state["err_version"])
        self._err_width = int(state.get("err_width", err.shape[1]))

    # -- gradients (backend seam, used directly by the equivalence tests) ---

    def _spmd_gradients(self, params: PyTree, partition_batch: dict, a, support) -> PyTree:
        # per-kernel spans (DESIGN.md §10/§12): the spmd backend's step span
        # splits into pack / the shard_map program (tagged with which wire
        # kernels ran inside it) / unravel, so obs_report's phase table shows
        # the encode+decode cost move when the fused wire path switches on
        tr = self.tracer
        with tr.span("phase.spmd.pack"):
            if self._unravel is None:
                self._unravel, width = wire_unraveler(params)
                self._err_width = width if self.compress else 1
            if self._err is None or self._err_version != self.codec.version:
                # first call, or a membership change / rebalance re-encoded
                # the plan: run the elastic rebuild — mesh + program
                # re-derived at the live m, retained workers' error-feedback
                # rows carried, joiners/leavers zeroed (DESIGN.md §13).  Must
                # precede the pack: its jit closes over the plan's
                # worker-axis shape.
                self._rebuild_spmd()
            if self._state_mesh_stale:
                # params may still be committed to the pre-rebuild device
                # set; the flag is cleared by step() once opt is re-placed too
                params = self._replicate_on_mesh(params)
            plan = self.codec.plan
            pids, _, mask = self._device_plan()
            pbatch = jax.tree.map(jnp.asarray, partition_batch)
            sb = self._pack_slots(pbatch, pids.reshape(-1))
            if support is None:
                coeff = self._dev_coeff_mask  # cached, re-uploaded only on rebalance
            else:
                # unfinished partitions never left the worker: mask their
                # slots out of the wire-format coded gradient g̃_w (on device
                # — the (m, k) mask is the only per-step upload)
                coeff = self._coeff_support(
                    self._dev_coeff_mask, pids, mask, self._support_dev(support)
                )
            a_dev = jnp.asarray(np.asarray(a) / plan.k, jnp.float32)
        with tr.span("phase.spmd.grads") as sp:
            flat, self._err = self._spmd_grads(params, sb, coeff, a_dev, self._err)
            if tr.enabled:
                jax.block_until_ready(flat)
                sp.set(kernels=(
                    "coded_encode_int8+all_gather(i8)+coded_decode_int8"
                    if self.wire_kernel
                    else "coded_reduce+psum(f32)"
                    + ("+quantize_int8" if self.compress else "")
                ))
        with tr.span("phase.spmd.unravel"):
            return self._unravel(flat)

    def gradients(self, params: PyTree, partition_batch: dict, a) -> PyTree:
        """Decoded gradient under decode vector ``a`` (ndarray, or a
        :class:`DecodeOutcome` carrying an optional partial-work mask) via
        the engine's backend.  All backends agree to float tolerance by
        construction — on exact AND inexact decodes."""
        a, support = self._split_decode(a)
        if self.backend == "fused":
            if self.host_pack:
                batch = {
                    k: jnp.asarray(v)
                    for k, v in self._flat_batch(partition_batch, a, support).items()
                }
                return self._fused_grads_host(params, batch)
            pids, coeff, mask = self._device_plan()
            pbatch = jax.tree.map(jnp.asarray, partition_batch)
            return self._fused_grads(
                params, pbatch, jnp.asarray(np.asarray(a), jnp.float32),
                self._support_dev(support), pids, coeff, mask,
            )
        if self.backend == "reference":
            decoded, _ = protocol_reference(
                self._slot_loss, params, partition_batch, self.codec.scheme,
                decode_vec=a, support=support, grad_fn=self._ref_grad,
            )
            return decoded
        return self._spmd_gradients(params, partition_batch, a, support)

    # -- the train step -----------------------------------------------------

    def step(
        self, state: TrainerState, partition_batch: dict[str, np.ndarray], a
    ) -> tuple[TrainerState, dict[str, float]]:
        """One optimizer step from a partition-major batch + decode vector
        (or :class:`DecodeOutcome` — inexact/partial steps use whatever
        arrived, shapes unchanged, so the jitted path never recompiles).

        Phase spans (DESIGN.md §10): with tracing on, the host-side cost of
        each step phase is a span on the wall clock and in the profiler's
        trace.  The fused backend is ONE XLA program: ``phase.upload`` (the
        step's small inputs), ``phase.dispatch`` (the jitted call until it
        returns; it carries ``rows``, the k·mb sequences through forward/
        backward, and ``slot_rows``, the m·n_slots·mb the code assigns) and
        ``phase.readback`` (the blocking metric reads, which wait for the
        device); the protocol backends expose their separable phases.
        Tracing off costs one no-op call per phase."""
        tr = self.tracer
        a_vec, support = self._split_decode(a)
        if self.backend == "fused" and self.host_pack:
            with tr.span("phase.pack+upload"):
                batch = {
                    k: jnp.asarray(v)
                    for k, v in self._flat_batch(partition_batch, a_vec, support).items()
                }
            with tr.span("phase.fused"):
                params, opt, metrics = self._fused_step_host(
                    state.params, state.opt, batch, jnp.asarray(state.step)
                )
                out = {k: float(v) for k, v in metrics.items()}  # blocks on device
        elif self.backend == "fused":
            with tr.span("phase.upload"):
                pids, coeff, mask = self._device_plan()
                pbatch = jax.tree.map(jnp.asarray, partition_batch)
                a_dev = jnp.asarray(np.asarray(a_vec), jnp.float32)
                sup_dev = self._support_dev(support)
            with tr.span("phase.dispatch") as sp:
                params, opt, metrics = self._fused_step(
                    state.params, state.opt, pbatch, a_dev,
                    sup_dev, pids, coeff, mask, jnp.asarray(state.step),
                )
                if tr.enabled:
                    # sequences through forward/backward vs what the code assigns
                    k, mb = jax.tree.leaves(pbatch)[0].shape[:2]
                    sp.set(rows=int(k * mb), slot_rows=int(pids.size * mb))
            with tr.span("phase.readback"):
                out = {k: float(v) for k, v in metrics.items()}  # blocks on device
        else:
            with tr.span("phase.pack+encode+wire+decode" if self.backend == "spmd"
                         else "phase.gradients"):
                grads = self.gradients(state.params, partition_batch, a)
                if self.backend == "spmd" and self._state_mesh_stale:
                    # a rebuild moved the mesh under this step: re-place the
                    # caller's (params, opt) onto it before the loss/apply
                    # jits mix them with new-mesh grads (device-to-device,
                    # values untouched — the resume stays bit-exact)
                    state = TrainerState(
                        params=self._replicate_on_mesh(state.params),
                        opt=self._replicate_on_mesh(state.opt),
                        step=state.step,
                    )
                    self._state_mesh_stale = False
            with tr.span("phase.loss"):
                pids, coeff, mask = self._device_plan()
                pbatch = jax.tree.map(jnp.asarray, partition_batch)
                loss = self._loss_fwd(
                    state.params, pbatch, jnp.asarray(np.asarray(a_vec), jnp.float32),
                    self._support_dev(support), pids, coeff, mask,
                )
            with tr.span("phase.apply"):
                params, opt, metrics = self._apply(
                    state.params, state.opt, grads, jnp.asarray(state.step)
                )
                metrics = {**metrics, "loss": loss}
                out = {k: float(v) for k, v in metrics.items()}  # blocks on device
        new_state = TrainerState(params=params, opt=opt, step=state.step + 1)
        return new_state, out
