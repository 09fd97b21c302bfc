"""Benchmark driver — one section per paper table/figure + kernels + roofline.

Prints ``name,us_per_call,derived`` CSV rows (then detailed per-bench CSVs)
and writes the same summary machine-readably to ``results/BENCH_run.json``
(per-section us_per_call + full-precision derived claims) so the perf
trajectory of the repo is diffable across commits.
Env: BENCH_FAST=1 shrinks iteration counts for CI-speed runs.
"""

from __future__ import annotations

import os
import sys
import time


def _fast() -> bool:
    return os.environ.get("BENCH_FAST", "0") == "1"


def main() -> None:
    from benchmarks import fig2_delay, fig3_clusters, fig4_convergence, fig5_resource_usage
    from benchmarks import fig6_approx, kernels_bench, roofline_table
    from benchmarks import resilience, scaling, serving, steptime

    t0 = time.time()
    all_rows = []
    summary = []  # (name, us_per_call, derived display string, claims dict)

    # --- Fig.2: delay sweep on Cluster-A ---
    t = time.time()
    rows = fig2_delay.run(n_iters=50 if _fast() else 200)
    claims = fig2_delay.derived_claims(rows)
    all_rows += rows
    summary.append(("fig2_delay", (time.time() - t) * 1e6 / max(len(rows), 1),
                    ";".join(f"{k}={v:.2f}" for k, v in claims.items()), claims))

    # --- Fig.3: clusters B/C/D ---
    t = time.time()
    rows = fig3_clusters.run(n_iters=40 if _fast() else 150)
    all_rows += rows
    het = {r["cluster"]: r["mean_iter_s"] for r in rows if r["scheme"] == "heter_aware"}
    cyc = {r["cluster"]: r["mean_iter_s"] for r in rows if r["scheme"] == "cyclic"}
    claims = {f"speedup_{c}": cyc[c] / het[c] for c in het}
    summary.append(("fig3_clusters", (time.time() - t) * 1e6 / max(len(rows), 1),
                    ";".join(f"{k}={v:.2f}" for k, v in claims.items()), claims))

    # --- Fig.4: convergence vs SSP (real training) ---
    t = time.time()
    rows = fig4_convergence.run(n_steps=12 if _fast() else 60)
    all_rows += rows
    finals = {}
    for r in rows:
        finals[r["scheme"]] = (r["sim_time_s"], r["loss"])
    claims = {}
    for s, (tt, l) in finals.items():
        claims[f"{s}_final_loss"] = l
        claims[f"{s}_final_t_s"] = tt
    summary.append(("fig4_convergence", (time.time() - t) * 1e6 / max(len(rows), 1),
                    ";".join(f"{s}:loss={l:.3f}@t={tt:.1f}s" for s, (tt, l) in finals.items()),
                    claims))

    # --- Fig.5: resource usage ---
    t = time.time()
    rows = fig5_resource_usage.run(n_iters=50 if _fast() else 200)
    all_rows += rows
    claims = {f"{r['scheme']}_resource_usage": r["resource_usage"] for r in rows}
    summary.append(("fig5_resource_usage", (time.time() - t) * 1e6 / max(len(rows), 1),
                    ";".join(f"{r['scheme']}={r['resource_usage']:.2f}" for r in rows), claims))

    # --- Fig.6: approximate/deadline stepping under misestimation ---
    t = time.time()
    rows = fig6_approx.run(n_steps=16 if _fast() else 60)
    claims = fig6_approx.derived_claims(rows)
    all_rows += rows
    summary.append(("fig6_approx", (time.time() - t) * 1e6 / max(len(rows), 1),
                    ";".join(f"{k}={v:.2f}" for k, v in claims.items()), claims))

    # --- step-time: device-resident vs host data path (DESIGN.md §6) ---
    t = time.time()
    rows = steptime.run(n_iters=8 if _fast() else 24)
    claims = steptime.derived_claims(rows)
    all_rows += rows
    summary.append(("steptime", (time.time() - t) * 1e6 / max(len(rows), 1),
                    ";".join(f"{k}={v:.2f}" for k, v in claims.items()), claims))

    # --- large-m control-plane scaling (DESIGN.md §7) ---
    t = time.time()
    rows = scaling.run()
    claims = scaling.derived_claims(rows)
    all_rows += rows
    summary.append(("scaling", (time.time() - t) * 1e6 / max(len(rows), 1),
                    ";".join(f"{k}={v:.2f}" for k, v in claims.items()), claims))

    # --- elastic membership remap (DESIGN.md §8) ---
    t = time.time()
    rows = scaling.run_membership()
    claims = scaling.membership_claims(rows)
    all_rows += rows
    summary.append(("membership", (time.time() - t) * 1e6 / max(len(rows), 1),
                    ";".join(f"{k}={v:.2f}" for k, v in claims.items()), claims))

    # --- coded serving: decode micro + SLO tail-latency gate (DESIGN.md §9) ---
    t = time.time()
    rows = serving.run()
    claims = serving.derived_claims(rows)
    all_rows += rows
    summary.append(("serving", (time.time() - t) * 1e6 / max(len(rows), 1),
                    ";".join(f"{k}={v:.2f}" for k, v in claims.items()), claims))

    # --- resilience: graceful degradation under faults (DESIGN.md §11) ---
    t = time.time()
    rows = resilience.run()
    claims = resilience.derived_claims(rows)
    all_rows += rows
    summary.append(("resilience", (time.time() - t) * 1e6 / max(len(rows), 1),
                    ";".join(f"{k}={v:.2f}" for k, v in claims.items()), claims))

    # --- kernels: wire-path roofline + structural claims (DESIGN.md §12) ---
    t = time.time()
    rows = kernels_bench.run()
    claims = kernels_bench.derived_claims(rows)
    all_rows += rows
    summary.append(("kernels", (time.time() - t) * 1e6 / max(len(rows), 1),
                    ";".join(f"{k}={v:.2f}" for k, v in claims.items()), claims))

    # --- roofline table from dry-run artifacts ---
    rows = roofline_table.run()
    all_rows += rows
    if rows:
        worst = min(rows, key=lambda r: r["mfu_at_roofline"] or 0)
        summary.append(("roofline_cells", float(len(rows)),
                        f"worst_mfu={worst['arch']}/{worst['shape']}={worst['mfu_at_roofline']:.4f}",
                        {"n_cells": len(rows), "worst_mfu": worst["mfu_at_roofline"],
                         "worst_cell": f"{worst['arch']}/{worst['shape']}"}))

    print("name,us_per_call,derived")
    for name, us, derived, _ in summary:
        print(f"{name},{us:.2f},{derived}")

    from benchmarks._util import BENCH_SCHEMA_VERSION, atomic_write_json

    atomic_write_json("results/bench_rows.json", all_rows)
    # machine-readable perf trajectory: per-section us_per_call + the derived
    # claims at full precision (the display strings above are rounded).
    # Atomic write + schema/timestamp envelope via benchmarks._util — a
    # crashed sweep never leaves a torn artifact.
    atomic_write_json("results/BENCH_run.json", {
        "schema_version": BENCH_SCHEMA_VERSION,
        "generated_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "fast": _fast(),
        "total_s": time.time() - t0,
        "n_detail_rows": len(all_rows),
        "sections": [
            {"name": name, "us_per_call": float(us), "derived": derived, "claims": claims}
            for name, us, derived, claims in summary
        ],
    })
    print(f"# {len(all_rows)} detail rows -> results/bench_rows.json; "
          f"summary -> results/BENCH_run.json (total {time.time() - t0:.1f}s)",
          file=sys.stderr)


if __name__ == "__main__":
    main()
