#!/usr/bin/env bash
# Tier-1 test gate: run from anywhere, extra pytest args pass through.
#   ./scripts/test.sh                    # full suite
#   ./scripts/test.sh tests/test_coding.py -k decode
#   RUN_TIER2=1 ./scripts/test.sh        # + tier-2: benchmark smoke (fig2-6)
set -euo pipefail
cd "$(dirname "$0")/.."
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m pytest -x -q "$@"
echo "== tier-1: spmd elastic rebuild (tests/spmd_driver.py engine_spmd_elastic, 8 fake devices) =="
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python tests/spmd_driver.py engine_spmd_elastic
if [[ "${RUN_TIER2:-0}" == "1" ]]; then
  echo "== tier-2: benchmark smoke (BENCH_FAST=1 benchmarks/run.py) =="
  make bench-smoke
  echo "== tier-2: large-m scaling gate (BENCH_FAST=1 benchmarks/scaling.py) =="
  make bench-scaling
  echo "== tier-2: membership churn soak (50 transitions, m up to 64) =="
  make churn-soak
  echo "== tier-2: coded-serving gate (BENCH_FAST=1 benchmarks/serving.py) =="
  make bench-serving
  echo "== tier-2: chaos soak (mixed crash/hang/flaky/corrupt runs at m=10) =="
  make chaos-soak
  echo "== tier-2: resilience gate (BENCH_FAST=1 benchmarks/resilience.py) =="
  make bench-resilience
  echo "== tier-2: kernel roofline gate (BENCH_FAST=1 benchmarks/kernels_bench.py) =="
  make bench-kernels
fi
