#!/usr/bin/env bash
# CI entrypoint.
#
#   scripts/ci.sh --fast    tier-1 unit tests only (the exact command
#                           ROADMAP.md documents) — the pre-commit loop
#   scripts/ci.sh           tier-1 tests PLUS smoke runs of the serving
#                           driver with a live add/remove round-trip, the
#                           heterogeneous-batch example with its mutating-
#                           corpus tail (request cache -> add -> invalidate
#                           -> remove), the Table-1 preprocessing benchmark
#                           through the clusterer seam (both FPF backends),
#                           and the serving-throughput benchmark (QPS vs
#                           batch size on every backend — off-TPU this runs
#                           the query-tiled bucket_score v2 kernel in
#                           interpret mode, so device-side schedule
#                           construction, tile padding and the fp32/bf16/
#                           int8 pack sweep are all exercised end to end,
#                           plus a second pass that builds an int8-packed
#                           index and serves every search through the
#                           exact-rescore tail), the async micro-batching
#                           serving tier (--serve: concurrent submits
#                           through repro.serving with a hard id/score
#                           parity check vs the synchronous path), the
#                           tiered retrieval paths (--exact: full-sweep
#                           exact tier hard-checked against brute force,
#                           also through the async micro-batcher;
#                           --min-recall: calibrated recall-floor
#                           escalation, floor checked on held-out
#                           queries), the closed-loop serving load
#                           test (micro-batched QPS vs the sequential
#                           baseline), and the chaos suite (fault
#                           injection into the replica pool: transient
#                           errors, a wedged replica, a flapping one, a
#                           failure storm — hard-asserting parity of
#                           non-degraded answers, honest degradation
#                           stamping, breaker trip AND recovery, and a
#                           bounded p99 under hangs), so regressions
#                           anywhere in the build->serve->mutate->fail
#                           path fail CI, not just unit tests
#
# Extra args are forwarded to pytest in both modes.
set -euo pipefail
cd "$(dirname "$0")/.."

FAST=0
ARGS=()
for a in "$@"; do
  case "$a" in
    --fast) FAST=1 ;;
    *) ARGS+=("$a") ;;
  esac
done

PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q ${ARGS[@]+"${ARGS[@]}"}

if [[ "$FAST" == 0 ]]; then
  echo "[ci] smoke: serving driver + incremental add/remove round-trip"
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.launch.serve --docs 2000 --queries 8 --mutate 4
  echo "[ci] smoke: heterogeneous batch + calibrated planning + mutating corpus"
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python examples/serve_retrieval.py --docs 2000 --queries 32
  echo "[ci] smoke: Table-1 preprocessing through the clusterer seam"
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.table1_preprocessing --scale quick
  echo "[ci] smoke: serving throughput (tiled bucket_score v2, interpret off-TPU)"
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.throughput --scale quick --backend reference
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.throughput --scale quick --backend fused
  echo "[ci] smoke: int8 quantised pack + exact-rescore tail"
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.throughput --scale quick --backend fused \
      --pack-dtype int8 --rescore 20
  echo "[ci] smoke: fused throughput over a 4-device forced CPU mesh (hard-"
  echo "      checks the bf16=1/2 / int8=1/4 packed-bytes-per-query ratios)"
  XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.throughput --scale quick --backend fused --batches 8
  echo "[ci] smoke: async serving tier (micro-batching, parity vs one-by-one)"
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.launch.serve --serve --docs 2000 --queries 64
  echo "[ci] smoke: tiered exact retrieval (full sweep, brute-force parity)"
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.launch.serve --docs 2000 --queries 16 --exact --serve
  echo "[ci] smoke: recall-floor escalation (calibrated ladder, exact ceiling)"
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.launch.serve --docs 2000 --queries 16 --probes 3 \
      --min-recall 0.95
  echo "[ci] smoke: serving load test (closed loop, reference backend)"
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.loadtest --scale quick --backend reference --mode closed
  echo "[ci] smoke: chaos suite (fault injection, hard parity/honesty/breaker"
  echo "      /p99 assertions inside the harness — any violation exits non-zero)"
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.loadtest --chaos --scale quick --backend reference
fi
