.PHONY: test test-fast serve bench bench-preprocess bench-throughput \
	bench-sharded bench-loadtest bench-chaos

# Tier-1 verify (ROADMAP.md) + serving/benchmark smokes (incl. add/remove)
test:
	./scripts/ci.sh

# Tier-1 only, minus the slow multi-device subprocess tests
test-fast:
	./scripts/ci.sh --fast -m "not slow"

serve:
	PYTHONPATH=src python -m repro.launch.serve --backend auto

bench:
	PYTHONPATH=src python -m benchmarks.run

# Build-side wall clock only: every registered clusterer through the seam
# (both FPF backends) + the paper's three Table-1 index builds
bench-preprocess:
	PYTHONPATH=src python -m benchmarks.table1_preprocessing --scale quick

# Serving QPS vs batch size: every backend, fused swept over the
# fp32/bf16/int8 bucket-major packs (labelled entries; interpret off-TPU)
bench-throughput:
	PYTHONPATH=src python -m benchmarks.throughput --scale quick

# The fused path over a forced 4-device CPU mesh: per-shard bucket-major
# packs, QPS per pack dtype, and the bf16=1/2 / int8=1/4 packed-bytes-per-
# query ratio checks (on TPU pods, drop XLA_FLAGS to use the real mesh)
bench-sharded:
	XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	PYTHONPATH=src python -m benchmarks.throughput --scale quick \
		--backend fused --batches 8

# Async serving tier under load: closed-loop (fixed concurrency) + open-loop
# (fixed arrival rate) vs the sequential one-by-one baseline
bench-loadtest:
	PYTHONPATH=src python -m benchmarks.loadtest --scale quick

# Chaos suite: closed-loop serving under injected faults (transient errors,
# slow/hung/flapping replicas, failure storm) with hard assertions — parity
# of non-degraded answers vs the sync path, min_recall/exact never silently
# degraded, breaker trips AND recovers under flap, bounded p99 under hangs
bench-chaos:
	PYTHONPATH=src python -m benchmarks.loadtest --chaos --scale quick \
		--backend reference
