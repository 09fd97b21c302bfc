# Repo task runner.  `make test` is the tier-1 gate (same command the CI
# driver runs); PYTHONPATH plumbing lives in scripts/test.sh so it stops
# being tribal knowledge.

.PHONY: test test-fast test-tier2 test-membership churn-soak chaos-soak bench bench-smoke bench-scaling bench-serving bench-resilience bench-kernels quickstart

test:
	./scripts/test.sh

test-fast:  ## skip the slow subprocess SPMD tests
	./scripts/test.sh --ignore=tests/test_spmd.py

test-membership:  ## elastic-membership churn harness (DESIGN.md §8)
	./scripts/test.sh tests/test_membership.py

churn-soak:  ## tier-2 churn soak: 50 random transitions at m up to 64
	CHURN_SOAK=1 ./scripts/test.sh tests/test_membership.py -k soak

chaos-soak:  ## tier-2 chaos soak: long mixed-fault runs at m=10 (DESIGN.md §11)
	CHAOS_SOAK=1 ./scripts/test.sh tests/test_resilience.py -k soak

test-tier2:  ## tier-1 suite + benchmark smoke (what CI's tier-2 gate runs)
	RUN_TIER2=1 ./scripts/test.sh

bench:  ## full-scale benchmark run (slow)
	PYTHONPATH=src:. python benchmarks/run.py

bench-smoke:  ## CI-speed benchmark smoke: all sections incl. fig6, shrunk iters
	PYTHONPATH=src:. BENCH_FAST=1 python benchmarks/run.py

bench-scaling:  ## large-m control-plane gate: m in {20,64,256} x schemes; fails if the m=256 budget is blown
	PYTHONPATH=src:. BENCH_FAST=1 python benchmarks/scaling.py

bench-serving:  ## coded-serving gate: decode micro + p99-TTFT >= 1.3x over wait-for-all at 30% stragglers
	PYTHONPATH=src:. BENCH_FAST=1 python benchmarks/serving.py

bench-resilience:  ## resilience gate: degraded time-to-target <= 1.5x fault-free under 1 crash + 1 hang
	PYTHONPATH=src:. BENCH_FAST=1 python benchmarks/resilience.py

bench-kernels:  ## kernel roofline gate: fused coded_reduce >= 1.0x axpy, pad-free trace, no f32 wire tensor, oracle bit-equality
	PYTHONPATH=src:. BENCH_FAST=1 python benchmarks/kernels_bench.py

quickstart:
	PYTHONPATH=src python examples/quickstart.py

# On a real TPU host, launch through scripts/run.sh for the hardened
# environment (tcmalloc, XLA step markers, quiet TF logging), e.g.:
#   ./scripts/run.sh python -m repro.launch.train --arch smollm-360m --reduced
