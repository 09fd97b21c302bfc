"""On-chip smoke check of the coded trainer.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # four TPU chips: the spmd backend only

One chip, three phases:

  (a) device check: the run stops, non-zero, unless JAX sees a TPU;
  (b) main path: ``repro.launch.train.main`` trains smollm-360m at its
      published widths — fused backend, heter_aware code over m=4 coded
      workers with s=1 and one permanently faulty worker, 8 steps at
      sequence length 512 — and every loss must be finite and the last below
      the first;
  (c) the three wire kernels, compiled, at the model's wire width D, against
      the oracles in ``repro.kernels.ref``.

Four chips (``--chips 4``), one phase: the spmd backend with one chip per
coded worker against the fused backend's gradient on the same batch, with
int8 wire compression off and on.

A failed check raises, so the script exits non-zero and prints no result.
On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Everything runs in this one process, which holds the chips until it exits.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.launch.runtime import device_info, enable_compilation_cache  # noqa: E402

ARCH = "smollm-360m"
EPS = float(np.finfo(np.float32).eps)
TINY = float(np.finfo(np.float32).tiny)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeError(RuntimeError):
    """A check of this script failed."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def require_tpu(chips: int) -> dict:
    """Phase (a): the devices JAX found, or exit non-zero when fewer than
    ``chips`` TPUs are there."""
    info = device_info()
    if info["platform"] != "tpu" or info["count"] < chips:
        raise SystemExit(f"chip_smoke: needs {chips} TPU chip(s), JAX found {info}")
    return info


# ---------------------------------------------------------------------------
# (b) main path
# ---------------------------------------------------------------------------


def train_phase(*, reduced: bool = False, seq_len: int = 512, steps: int = 8) -> dict:
    """Train through the launcher's own entry point and check the losses."""
    from repro.launch import train

    compile_s = []

    def on_duration(event, secs, **_):
        if event == _COMPILE_EVENT:
            compile_s.append(secs)

    argv = [
        "--arch", ARCH, "--backend", "fused", "--scheme", "heter_aware",
        "--m", "4", "--s", "1", "--part-mb", "2", "--seq-len", str(seq_len),
        "--straggler", "fault", "--steps", str(steps), "--log-every", "1",
    ] + (["--reduced"] if reduced else [])
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        summary = train.main(argv)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)

    losses = np.asarray(summary["losses"], np.float64)
    check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    check(np.all(np.isfinite(losses)), f"non-finite loss: {losses.tolist()}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses.tolist()}")
    walls = summary["step_wall_s"]
    steady = float(np.median(walls[2:] if len(walls) > 2 else walls[1:]))
    stats = jax.devices()[0].memory_stats() or {}
    print(f"main path: {ARCH}{' (reduced)' if reduced else ''}, "
          f"{summary['n_params']} parameters, seq_len {seq_len}, {steps} steps")
    print(f"losses: {losses.tolist()}")
    print(f"compile time: {sum(compile_s)} s (XLA backend compiles of the phase)")
    print(f"steady step wall time: {steady} s (median after the first two "
          f"steps; each step's metrics are read back from the device)")
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}",
          flush=True)
    return summary


# ---------------------------------------------------------------------------
# (c) wire kernels at the model's D
# ---------------------------------------------------------------------------


def wire_width(cfg) -> int:
    """D of the spmd wire for ``cfg``: its parameters flattened, lane-padded."""
    from repro.core.aggregator import wire_unraveler
    from repro.models.lm import build_model

    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    return wire_unraveler(shapes)[1]


_CHUNK = 1 << 20  # columns per step of the checks below


def _column_max(fn, n: int, *cols):
    """Elementwise maxima of ``fn``'s ``n`` outputs over column chunks of
    ``cols`` (arrays sharing their last dim D).  The oracle's temporaries
    stay one chunk wide: at the model's D the kernel's operands and outputs
    leave no room on the chip for a full-width reference beside them."""
    D = cols[0].shape[-1]
    c = min(_CHUNK, D)

    def body(i, acc):
        start = jnp.minimum(i * c, D - c)  # the last chunk overlaps; max does not mind
        part = [jax.lax.dynamic_slice_in_dim(x, start, c, axis=x.ndim - 1) for x in cols]
        return jnp.maximum(acc, jnp.stack(fn(*part)))

    return jax.lax.fori_loop(0, -(-D // c), body, jnp.full((n,), -jnp.inf, jnp.float32))


def _abs_weighted(g, w):
    """Σ_p |w_p·g_p| elementwise: the scale of the f32 reduction-order bound."""
    return sum(jnp.abs(w[p] * g[p].astype(jnp.float32)) for p in range(g.shape[0]))


@jax.jit
def _reduce_gap(g, w, out):
    """max |out − oracle| in units of the f32 reduction-order bound
    2·P·eps·Σ_p|w_p·g_p| (≤ 1 agrees)."""
    from repro.kernels import ref

    def gap(g, out):
        want = ref.coded_reduce_ref(g.astype(jnp.float32), w)
        bound = 2 * g.shape[0] * EPS * _abs_weighted(g, w) + TINY
        return [jnp.max(jnp.abs(out - want) / bound)]

    return _column_max(gap, 1, g, out)[0]


@jax.jit
def _encode_gaps(g, w, err, q, scale, new_err):
    """The encode's contract: dequantize(q, scale) + new_err equals
    coded_reduce + err (units of its f32 bound), the scale is max|coded|/127
    (relative gap), and |new_err| ≤ scale/2 (units of scale/2)."""
    from repro.kernels import ref

    def gaps(g, err, q, new_err):
        coded = ref.coded_reduce_ref(g, w) + err
        got = ref.dequantize(q, scale) + new_err
        bound = 2 * (g.shape[0] + 1) * EPS * (_abs_weighted(g, w) + jnp.abs(err) + scale)
        return [jnp.max(jnp.abs(got - coded) / bound), jnp.max(jnp.abs(coded)),
                jnp.max(jnp.abs(new_err))]

    gap, coded_max, err_max = _column_max(gaps, 3, g, err, q, new_err)
    want_scale = coded_max * jnp.float32(1.0 / 127.0)
    return gap, jnp.abs(scale - want_scale) / want_scale, err_max / (0.5 * scale)


def kernel_phase(D: int, *, P: int = 4, m: int = 4, seed: int = 0) -> None:
    """coded_reduce (P rows), the fused int8 encode (P rows) and the int8
    decode (m ≤ P payloads) at width D, compiled for the chip.  P=4: an f32
    (P, D) operand is stored in tiles of 8 rows once P exceeds 4, and at the
    model's D a (5, D) stack takes 12 GB of the chip's 16."""
    from repro.kernels.coded_reduce import coded_reduce_pallas
    from repro.kernels.wire import coded_decode_int8_pallas, coded_encode_int8_pallas

    kg, kw, ke, ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    g = jax.random.normal(kg, (P, D), jnp.float32)
    w = jax.random.normal(kw, (P,), jnp.float32)
    err = 0.01 * jax.random.normal(ke, (D,), jnp.float32)
    # the oracles' f32 dots must not round to bf16 on the chip
    with jax.default_matmul_precision("highest"):
        gap = float(_reduce_gap(g, w, coded_reduce_pallas(g, w)))
        print(f"coded_reduce P={P} D={D}: gap {gap} of the f32 bound", flush=True)
        check(gap <= 1.0, f"coded_reduce disagrees with ref: {gap}")

        q, scale, new_err = coded_encode_int8_pallas(g, w, err)
        gap, scale_gap, err_ratio = map(float, _encode_gaps(g, w, err, q, scale, new_err))
        print(f"coded_encode_int8 P={P} D={D}: gap {gap} of the f32 bound, scale "
              f"rel gap {scale_gap}, max|new_err| {err_ratio} of scale/2", flush=True)
        check(gap <= 1.0, f"encode: dequantize(q)+new_err != reduce+err: {gap}")
        check(scale_gap <= 1e-5, f"encode scale is not max|coded|/127: {scale_gap}")
        # q = round(fl(coded/scale)): the division's rounding may carry q
        # past the midpoint by 127·eps/2, and new_err itself rounds once
        check(err_ratio <= 1.0 + 128 * EPS, f"encode residual exceeds scale/2: {err_ratio}")
        del q, new_err, err

        # int8 payloads spanning the wire's range, from g's first rows (one
        # fused program: eager steps would each hold a copy of g's rows)
        qd = jax.jit(
            lambda g: jnp.clip(jnp.round(40.0 * g[:m]), -127, 127).astype(jnp.int8)
        )(g)
        del g
        ws = 1e-3 * jax.random.normal(ks, (m,), jnp.float32)
        gap = float(_reduce_gap(qd, ws, coded_decode_int8_pallas(qd, ws)))
        print(f"coded_decode_int8 m={m} D={D}: gap {gap} of the f32 bound", flush=True)
        check(gap <= 1.0, f"int8 decode disagrees with ref: {gap}")


# ---------------------------------------------------------------------------
# four chips: spmd backend against fused
# ---------------------------------------------------------------------------


def _host_leaves(tree) -> list[np.ndarray]:
    return [np.asarray(x, np.float32) for x in jax.tree.leaves(tree)]


def _check_spread(grads, params, mesh) -> None:
    """The decoded gradient and the replicated parameters live on every
    chip of the mesh, and every chip holds bytes."""
    devs = set(mesh.devices.flat)
    check(len(devs) == 4, f"mesh spans {len(devs)} devices")
    for name, tree in (("decoded gradient", grads), ("parameters", params)):
        for leaf in jax.tree.leaves(tree):
            check(leaf.sharding.device_set == devs,
                  f"{name} on {leaf.sharding.device_set}, not the mesh")
    for d in sorted(devs, key=lambda d: d.id):
        used = (d.memory_stats() or {}).get("bytes_in_use")
        print(f"  device {d.id} ({d.platform}): bytes_in_use {used}")
        # the host platform keeps no per-device accounting (fake devices)
        check(d.platform != "tpu" or (used or 0) > 0, f"device {d.id} holds no bytes")


SPMD_LAYERS = 24  # of smollm-360m's 32: see spmd_phase
SPMD_PART_MB = 1  # the float32 fused reference overflows one chip at 2


def spmd_phase(*, reduced: bool = False, seq_len: int = 512) -> None:
    """One gradient under a fixed decode vector (worker 3 straggles) from the
    spmd backend, compress off and on, against the fused backend's.

    float32 parameters under ``highest`` matmul precision, so that with
    compress off the two paths differ by f32 reduction order alone; with
    compress on the gap is bounded by the int8 quantisation:
    Σ_w |a_w/k|·scale_w/2, scale_w = max|g̃_w|/127 on a zero error buffer.
    At the published widths in float32 each worker's (n_slots, D) gradient
    stack leaves no room on one v5e chip for all 32 layers, so the depth is
    cut to :data:`SPMD_LAYERS` (ignored when ``reduced``).
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.configs import CodingConfig, TrainConfig, get_config
    from repro.core.codec import Codec
    from repro.data.pipeline import SyntheticData
    from repro.launch.mesh import make_auto_mesh
    from repro.models.lm import build_model
    from repro.train.engine import StepEngine

    cfg = get_config(ARCH)
    cfg = cfg.reduced() if reduced else dataclasses.replace(cfg, n_layers=SPMD_LAYERS)
    cfg = dataclasses.replace(cfg, dtype="float32")
    model = build_model(cfg)
    codec = Codec.from_config(CodingConfig(scheme="heter_aware", s=1), m=4, rng=1)
    batch = SyntheticData(cfg, k=codec.k, part_mb=SPMD_PART_MB, seq_len=seq_len).batch(0)
    a = codec.decode_vector([0, 1, 2])
    tc = TrainConfig()
    print(f"spmd vs fused: {ARCH}{' (reduced)' if reduced else ''} "
          f"{cfg.n_layers} layers float32, m=4 "
          f"k={codec.k} n_slots={codec.n_slots}, part_mb {SPMD_PART_MB}, seq_len "
          f"{seq_len}, decode vector {a.tolist()}", flush=True)

    with jax.default_matmul_precision("highest"):
        params = model.init(jax.random.PRNGKey(0))
        fused = StepEngine(model, tc, codec, backend="fused")
        want = _host_leaves(fused.gradients(params, batch, a))
        mesh = make_auto_mesh((4, 1), ("data", "model"))
        params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))

        off = StepEngine(model, tc, codec, backend="spmd", mesh=mesh,
                         compress=False, wire_kernel=False)
        got = off.gradients(params, batch, a)
        _check_spread(got, params, mesh)
        got_off = _host_leaves(got)
        del got
        # each worker's coded gradient g̃_w: the decode at a = k·e_w
        scales = []
        for w in range(codec.m):
            e = np.zeros(codec.m)
            e[w] = codec.k
            gw = off.gradients(params, batch, e)
            scales.append(max(float(jnp.max(jnp.abs(x))) for x in jax.tree.leaves(gw)) / 127)
            del gw
        del off

        on = StepEngine(model, tc, codec, backend="spmd", mesh=mesh,
                        compress=True, wire_kernel=True)
        got_on = _host_leaves(on.gradients(params, batch, a))

    norm = np.sqrt(sum(float(np.sum(x.astype(np.float64) ** 2)) for x in want))
    diff = np.sqrt(sum(float(np.sum((o.astype(np.float64) - f) ** 2))
                       for o, f in zip(got_off, want)))
    rel = diff / norm
    print(f"compress off: |spmd − fused| / |fused| = {rel} (f32 reduction order; "
          f"limit 1e-4), max abs {max(float(np.max(np.abs(o - f))) for o, f in zip(got_off, want))}")
    check(rel <= 1e-4, f"spmd (compress off) disagrees with fused: {rel}")

    qbound = sum(abs(float(aw)) / codec.k * s * (0.5 + 1e-3) for aw, s in zip(a, scales))
    excess = max(
        float(np.max(np.abs(c - f) - np.abs(o - f)))
        for c, o, f in zip(got_on, got_off, want)
    )
    print(f"compress on (fused int8 wire kernels): max |spmd − fused| beyond the "
          f"compress-off gap = {excess}, int8 bound {qbound} (scales {scales})", flush=True)
    check(excess <= qbound, f"spmd (compress on) exceeds the int8 bound: {excess} > {qbound}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the spmd-vs-fused phase on four chips")
    args = ap.parse_args(argv)
    info = require_tpu(args.chips)
    print(f"device: {info}", flush=True)
    enable_compilation_cache()
    if args.chips == 4:
        spmd_phase()
    else:
        from repro.configs import get_config

        train_phase()
        gc.collect()  # the trainer's reference cycles hold its device state
        kernel_phase(wire_width(get_config(ARCH)))
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
