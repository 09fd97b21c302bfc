"""Measurement-driven kernel selection (DESIGN.md §12).

Every "fastest" claim in the kernel layer is backed by a timing on the host
that will run it, not by an assumption:

  - :func:`interleaved_best_us` is the one timing discipline everything
    shares (benchmarks/kernels_bench.py included): candidates are timed in
    INTERLEAVED rounds so machine-load drift hits all of them equally, and
    the per-candidate best round is kept — a contended round measures the
    machine, not the code.  ``block_until_ready`` on the actual output, not
    a dispatch timer.
  - :func:`best_tile_d` autotunes ``coded_reduce_pallas``'s lane tile on
    TPU (the only backend that compiles Pallas); elsewhere it returns None
    (use the default ``TILE_D``).
  - :func:`best_reduce_schedule` picks the fastest XLA schedule for the
    (P,)·(P, D) reduction on non-TPU hosts, where ``impl="best"`` cannot
    mean a Pallas kernel.  The candidates genuinely differ: the unrolled
    mul-add chain beats the degenerate (1, P) gemm ~1.7x at small P on the
    reference host, while einsum wins at larger P.
  - :func:`wire_kernel_default` decides whether the spmd wire path uses the
    fused int8 kernels when the caller leaves ``wire_kernel=None``: True
    only on TPU AND only if the fused encode beats the unfused composition
    in a probe on this very host.  Non-TPU answers False immediately with
    no timing cost — interpret-mode wall clock is meaningless and the tests
    that sweep engines must not pay for a probe.

All probes are cached per (question, shape) for the process lifetime.
"""

from __future__ import annotations

import functools
import time
from typing import Callable

import jax
import jax.numpy as jnp

_CACHE: dict = {}

TILE_CANDIDATES = (512, 1024, 2048)


def interleaved_best_us(
    fns: dict[str, Callable[[], object]],
    *,
    rounds: int = 4,
    iters: int = 3,
    warmup: int = 2,
) -> dict[str, float]:
    """Best-of-interleaved-rounds wall time (µs per call) for each candidate.

    ``fns`` map name → nullary callable returning a jax value (blocked on
    via ``jax.block_until_ready``, so async dispatch cannot make a slow
    kernel look fast).  Warmup calls absorb compilation.
    """
    for fn in fns.values():
        for _ in range(warmup):
            jax.block_until_ready(fn())
    best = {name: float("inf") for name in fns}
    for _ in range(rounds):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            out = None
            for _ in range(iters):
                out = fn()
            jax.block_until_ready(out)
            best[name] = min(best[name], (time.perf_counter() - t0) / iters * 1e6)
    return best


def best_tile_d(P: int, D: int) -> int | None:
    """Autotuned lane tile for ``coded_reduce_pallas`` at (P, D) — TPU only.

    Returns None off-TPU (caller falls back to the default ``TILE_D``).
    """
    if jax.default_backend() != "tpu":
        return None
    key = ("tile_d", P, D)
    if key not in _CACHE:
        from repro.kernels.coded_reduce import coded_reduce_pallas

        g = jnp.zeros((P, D), jnp.float32)
        w = jnp.ones((P,), jnp.float32)
        cands = [t for t in TILE_CANDIDATES if t <= max(D, TILE_CANDIDATES[0])]
        times = interleaved_best_us(
            {str(t): functools.partial(coded_reduce_pallas, g, w, tile_d=t)
             for t in cands}
        )
        choice = int(min(times, key=times.get))
        _CACHE[key] = choice
    return _CACHE[key]


# beyond this the unrolled chain's graph size (and register pressure)
# outweighs the fusion win; measured crossover is well below it
_UNROLL_MAX_P = 64


def _unrolled_reduce(w: jnp.ndarray, g: jnp.ndarray) -> jnp.ndarray:
    acc = w[0] * g[0]
    for p in range(1, g.shape[0]):
        acc = acc + w[p] * g[p]
    return acc


def best_reduce_schedule(P: int, D: int) -> str:
    """Fastest XLA schedule for the (P,)·(P, D) reduction on this host.

    Candidates are algebraically identical single-pass forms XLA lowers to
    different loop nests: ``matmul`` (1,P)@(P,D), ``einsum`` p,pd->d,
    ``tensordot``, and (at P <= 64) the unrolled mul-add chain, which XLA
    fuses into one elementwise pass.  Cached per shape.  (On TPU the Pallas kernel is used
    instead — see ``ops.coded_reduce`` ``impl="best"``.)
    """
    key = ("reduce_schedule", P, D)
    if key not in _CACHE:
        g = jnp.zeros((P, D), jnp.float32)
        w = jnp.ones((P,), jnp.float32)
        cands = {
            "matmul": jax.jit(lambda w, g: (w[None, :] @ g)[0]),
            "einsum": jax.jit(lambda w, g: jnp.einsum("p,pd->d", w, g)),
            "tensordot": jax.jit(lambda w, g: jnp.tensordot(w, g, axes=1)),
        }
        if P <= _UNROLL_MAX_P:
            # the unrolled mul-add chain fuses into one elementwise pass;
            # at small P it beats the degenerate (1, P) gemm on CPU hosts
            cands["unroll"] = jax.jit(_unrolled_reduce)
        times = interleaved_best_us(
            {n: functools.partial(f, w, g) for n, f in cands.items()}
        )
        choice = min(times, key=times.get)
        _CACHE[key] = choice
    return _CACHE[key]


def xla_reduce(g: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """The autotuned XLA schedule applied: host-side ``impl="best"`` body."""
    sched = best_reduce_schedule(*g.shape)
    wf = w.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    if sched == "matmul":
        out = (wf[None, :] @ gf)[0]
    elif sched == "tensordot":
        out = jnp.tensordot(wf, gf, axes=1)
    elif sched == "unroll":
        out = _unrolled_reduce(wf, gf)
    else:
        out = jnp.einsum("p,pd->d", wf, gf)
    return out.astype(g.dtype)


def wire_kernel_default(P: int = 8, D: int = 1 << 16) -> bool:
    """Should the spmd wire path use the fused int8 kernels by default?

    True only on TPU and only when the fused encode measures faster than
    the unfused composition (reduce kernel + XLA quantize) at a
    representative shape on THIS host — the flag the engine resolves when
    ``CodingConfig.wire_kernel`` is None.  Off-TPU: False, instantly.
    """
    if jax.default_backend() != "tpu":
        return False
    key = ("wire_kernel", P, D)
    if key not in _CACHE:
        from repro.kernels import ref
        from repro.kernels.coded_reduce import coded_reduce_pallas
        from repro.kernels.wire import coded_encode_int8_pallas

        g = jnp.zeros((P, D), jnp.float32)
        w = jnp.ones((P,), jnp.float32)
        err = jnp.zeros((D,), jnp.float32)
        unfused = jax.jit(
            functools.partial(ref.encode_int8_ref, reduce_fn=coded_reduce_pallas)
        )
        times = interleaved_best_us({
            "fused": functools.partial(coded_encode_int8_pallas, g, w, err),
            "unfused": functools.partial(unfused, g, w, err),
        })
        choice = times["fused"] <= times["unfused"]
        _CACHE[key] = choice
    return _CACHE[key]
