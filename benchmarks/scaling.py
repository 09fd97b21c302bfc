"""Large-m scalability benchmark (DESIGN.md §7 acceptance gate).

Sweeps m ∈ {20, 64, 256} × schemes on the host control plane and records,
per (m, scheme):

  - ``plan_build_ms``        — registry construction (allocation + B +
    groups) — the elastic-rebalance hot path;
  - ``first_decodable_ms``   — one iteration's earliest-decodable search
    over the arrival stream (the tracker-driven Eq. 3 resolve);
  - ``decode_cold_us`` / ``decode_warm_us`` — decode-vector solve for a
    straggler pattern, cold (first solve) and warm (LRU hit).

Standalone (``make bench-scaling``, tier-2 CI) it also ENFORCES the
acceptance budget — m=256 heter-aware plan build + first-decodable check
under :data:`BUDGET_S` seconds — exiting nonzero on regression, and merges
its section into ``results/BENCH_run.json`` so the perf trajectory stays
diffable.  ``benchmarks/run.py`` embeds the same rows as a section.

Env: BENCH_FAST=1 shrinks repetitions/profiles (sizes stay — the gate IS
the large-m case).
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from repro.core import ClusterSim, DecodeError, FixedDelayStragglers, get_scheme

M_SWEEP = (20, 64, 256)
# s=3 so fractional repetition's (s+1) | m holds across the sweep and the
# uniform group-based load k(s+1)/m divides k (tiling chains exist)
S = 3
SCHEMES = ("heter_aware", "group_based", "cyclic", "fractional_repetition", "bernoulli")
BUDGET_S = 2.0  # acceptance: m=256 heter-aware build + first-decodable

# elastic membership (DESIGN.md §8): in-place grow/shrink remap budget
MEMBERSHIP_M = (20, 64)
MEMBERSHIP_SCHEMES = ("heter_aware", "group_based", "bernoulli")
MEMBERSHIP_BUDGET_MS = 250.0  # acceptance: m=64 heter-aware remap < 250 ms

# spmd engine rebuild (DESIGN.md §13): churn-to-first-step on an 8-device
# mesh (m=8→7→8, re-jit + err carry + post-transition step included)
SPMD_REBUILD_BUDGET_MS = 5000.0


def _fast() -> bool:
    return os.environ.get("BENCH_FAST", "0") == "1"


def _speeds(m: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.uniform(1.0, 4.0, m)


def bench_one(scheme: str, m: int, *, n_profiles: int, reps: int, seed: int = 0) -> dict:
    c = _speeds(m, seed)
    k = 2 * m if scheme in ("heter_aware", "group_based", "bernoulli") else m

    t0 = time.perf_counter()
    code = get_scheme(scheme, m=m, k=k, s=S, c=c, rng=seed)
    build_ms = (time.perf_counter() - t0) * 1e3

    # rebuild cost (the elastic-rebalance path) — timed on a THROWAWAY
    # instance so the gated measurements below run on the allocation that
    # matches `c`; best-of to strip jitter
    rebuild_ms = build_ms
    if code.supports_rebalance:
        scratch = get_scheme(scheme, m=m, k=k, s=S, c=c, rng=seed)
        rebuild_ms = min(
            _timed_ms(lambda r=r: scratch.rebalance(_speeds(m, seed + r + 1)))
            for r in range(reps)
        )

    sim = ClusterSim(code, c, comm_time=0.005, wait_for_all=code.wait_for_all)
    model = FixedDelayStragglers(S, np.inf)
    rng = np.random.default_rng(seed)

    first_ms, n_ok = [], 0
    for _ in range(n_profiles):
        profile = model.sample(m, rng)
        pt = sim.partition_times(profile)
        t0 = time.perf_counter()
        try:
            tau, used = code.earliest_decodable(pt.finish)
            n_ok += 1
        except DecodeError:
            pass  # >s effective stragglers for this profile: a real miss
        first_ms.append((time.perf_counter() - t0) * 1e3)

    # decode-vector solve for one straggler pattern: cold vs LRU-warm
    dead = rng.choice(m, size=S, replace=False)
    avail = [i for i in range(m) if i not in set(int(d) for d in dead)]
    code._reset_decode_cache()
    t0 = time.perf_counter()
    code.decode_outcome(avail)
    decode_cold_us = (time.perf_counter() - t0) * 1e6
    decode_warm_us = min(
        _timed_ms(lambda: code.decode_outcome(avail)) * 1e3 for _ in range(reps)
    )

    return {
        "bench": "scaling", "scheme": scheme, "m": m, "k": k, "s": S,
        "plan_build_ms": build_ms,
        "rebuild_ms": rebuild_ms,
        "first_decodable_ms": float(np.median(first_ms)),
        "first_decodable_max_ms": float(np.max(first_ms)),
        "decodable_fraction": n_ok / max(n_profiles, 1),
        "decode_cold_us": decode_cold_us,
        "decode_warm_us": decode_warm_us,
        "n_groups": len(code.scheme.groups),
    }


def _timed_ms(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return (time.perf_counter() - t0) * 1e3


def bench_membership_one(scheme: str, m: int, *, reps: int, seed: int = 0) -> dict:
    """In-place grow/shrink remap cost (DESIGN.md §8): build an
    ElasticController at m workers, time add_workers(+2) / remove_workers(2)
    transitions (best-of-reps on fresh controllers so every measurement is a
    cold remap of the same shape), record moved copies vs the bound."""
    from repro.core import Codec
    from repro.train.elastic import ElasticController

    k = 2 * m

    def _mk():
        c = _speeds(m, seed)
        code = get_scheme(scheme, m=m, k=k, s=S, c=c, rng=seed)
        return ElasticController(Codec(code), true_speeds=c, c_init=c)

    grow_ms, shrink_ms = [], []
    grow_stats = shrink_stats = None
    for r in range(reps):
        ctl = _mk()
        joins = _speeds(2, seed + 100 + r)
        t0 = time.perf_counter()
        grow_stats = ctl.add_workers(joins)
        grow_ms.append((time.perf_counter() - t0) * 1e3)
        t0 = time.perf_counter()
        shrink_stats = ctl.remove_workers([0, m // 2])
        shrink_ms.append((time.perf_counter() - t0) * 1e3)
    return {
        "bench": "membership", "scheme": scheme, "m": m, "k": k, "s": S,
        "grow_remap_ms": float(np.min(grow_ms)),
        "shrink_remap_ms": float(np.min(shrink_ms)),
        "grow_moved": int(grow_stats.moved),
        "grow_bound": -1 if grow_stats.bound is None else int(grow_stats.bound),
        "shrink_moved": int(shrink_stats.moved),
        "shrink_bound": -1 if shrink_stats.bound is None else int(shrink_stats.bound),
        "changed_columns": (
            -1 if grow_stats.changed_columns is None else int(grow_stats.changed_columns)
        ),
    }


def run_membership(ms=MEMBERSHIP_M, schemes=MEMBERSHIP_SCHEMES, seed: int = 0):
    reps = 2 if _fast() else 5
    return [
        bench_membership_one(scheme, m, reps=reps, seed=seed)
        for m in ms for scheme in schemes
    ]


def membership_claims(rows) -> dict[str, float]:
    claims = {}
    for r in rows:
        key = f"{r['scheme']}_m{r['m']}"
        claims[f"remap_ms_{key}"] = max(r["grow_remap_ms"], r["shrink_remap_ms"])
        claims[f"moved_{key}"] = float(r["grow_moved"] + r["shrink_moved"])
    worst = max(
        (
            max(r["grow_remap_ms"], r["shrink_remap_ms"])
            for r in rows
            if r["scheme"] == "heter_aware" and r["m"] == max(MEMBERSHIP_M)
        ),
        default=float("inf"),
    )
    claims[f"accept_m{max(MEMBERSHIP_M)}_remap_ms"] = worst
    return claims


def run(ms=M_SWEEP, schemes=SCHEMES, seed: int = 0):
    n_profiles = 3 if _fast() else 10
    reps = 2 if _fast() else 5
    rows = []
    for m in ms:
        for scheme in schemes:
            rows.append(bench_one(scheme, m, n_profiles=n_profiles, reps=reps, seed=seed))
    return rows


def derived_claims(rows) -> dict[str, float]:
    """Headline: the acceptance budget + how build/first-decode scale."""
    claims = {}
    for r in rows:
        if r["scheme"] == "heter_aware":
            claims[f"heter_build_ms_m{r['m']}"] = r["plan_build_ms"]
            claims[f"heter_first_decode_ms_m{r['m']}"] = r["first_decodable_ms"]
    big = [r for r in rows if r["scheme"] == "heter_aware" and r["m"] == max(r2["m"] for r2 in rows)]
    if big:
        r = big[0]
        claims["accept_m256_total_s"] = (
            r["plan_build_ms"] + r["first_decodable_max_ms"]
        ) / 1e3
        claims["accept_m256_decodable_fraction"] = r["decodable_fraction"]
    return claims


def run_spmd_rebuild() -> dict[str, float]:
    """Time the §13 spmd engine rebuild in a subprocess: it needs its own
    8-fake-device topology (XLA_FLAGS is per-process), so the measurement
    cannot run in this interpreter.  The child is held to the host CPU —
    fake devices exist only there — and :func:`main` starts it before this
    process initialises any JAX backend, so on a TPU host the parent never
    holds a chip while a JAX child runs.  Returns the claims dict printed by
    ``benchmarks/spmd_elastic.py``."""
    import json
    import subprocess

    script = os.path.join(os.path.dirname(__file__), "spmd_elastic.py")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, script], capture_output=True, text=True, timeout=560,
        env={**env, "JAX_PLATFORMS": "cpu"},
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"spmd_elastic benchmark failed:\n{proc.stdout}\n{proc.stderr}"
        )
    return {
        f"membership_{k}": float(v)
        for k, v in json.loads(proc.stdout.strip().splitlines()[-1]).items()
    }


def _merge_into_bench_run(name: str, claims: dict) -> None:
    """Standalone runs keep results/BENCH_run.json current (atomic +
    schema-stamped via benchmarks._util)."""
    from benchmarks._util import merge_into_bench_run

    merge_into_bench_run(name, claims, fast=_fast())


def main() -> int:
    rebuild_claims = run_spmd_rebuild()  # first: its JAX child needs a JAX-free parent
    rows = run()
    claims = derived_claims(rows)
    claims.update(rebuild_claims)
    print("scheme,m,plan_build_ms,first_decodable_ms,decode_cold_us,decode_warm_us,n_groups")
    for r in rows:
        print(
            f"{r['scheme']},{r['m']},{r['plan_build_ms']:.2f},"
            f"{r['first_decodable_ms']:.2f},{r['decode_cold_us']:.1f},"
            f"{r['decode_warm_us']:.1f},{r['n_groups']}"
        )
    _merge_into_bench_run("scaling", claims)

    mrows = run_membership()
    mclaims = membership_claims(mrows)
    print("scheme,m,grow_remap_ms,shrink_remap_ms,grow_moved,grow_bound,shrink_moved,shrink_bound,changed_columns")
    for r in mrows:
        print(
            f"{r['scheme']},{r['m']},{r['grow_remap_ms']:.2f},{r['shrink_remap_ms']:.2f},"
            f"{r['grow_moved']},{r['grow_bound']},{r['shrink_moved']},"
            f"{r['shrink_bound']},{r['changed_columns']}"
        )
    _merge_into_bench_run("membership", mclaims)

    total = claims.get("accept_m256_total_s", float("inf"))
    print(f"# m=256 heter-aware build+first-decodable: {total:.3f}s "
          f"(budget {BUDGET_S}s) -> results/BENCH_run.json", file=sys.stderr)
    if total >= BUDGET_S:
        print(f"FAIL: large-m budget blown ({total:.3f}s >= {BUDGET_S}s)", file=sys.stderr)
        return 1
    if claims.get("accept_m256_decodable_fraction", 0.0) <= 0.0:
        # a gate that only times a decode path must also prove it decodes
        print("FAIL: m=256 heter-aware never decoded a profile", file=sys.stderr)
        return 1
    remap = mclaims.get(f"accept_m{max(MEMBERSHIP_M)}_remap_ms", float("inf"))
    print(f"# m={max(MEMBERSHIP_M)} heter-aware membership remap: {remap:.1f}ms "
          f"(budget {MEMBERSHIP_BUDGET_MS}ms)", file=sys.stderr)
    if remap >= MEMBERSHIP_BUDGET_MS:
        print(f"FAIL: membership remap budget blown ({remap:.1f}ms >= "
              f"{MEMBERSHIP_BUDGET_MS}ms)", file=sys.stderr)
        return 1
    rebuild = claims.get("membership_spmd_rebuild_ms", float("inf"))
    print(f"# m=8→7→8 spmd engine rebuild (churn-to-first-step): "
          f"{rebuild:.0f}ms (budget {SPMD_REBUILD_BUDGET_MS:.0f}ms)",
          file=sys.stderr)
    if rebuild >= SPMD_REBUILD_BUDGET_MS:
        print(f"FAIL: spmd rebuild budget blown ({rebuild:.0f}ms >= "
              f"{SPMD_REBUILD_BUDGET_MS:.0f}ms)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
