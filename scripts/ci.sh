#!/usr/bin/env bash
# CI entrypoint: tier-1 correctness, then tier 2 (multi-client
# contention tests and chaos fault-injection tests).
#
#   scripts/ci.sh            # non-zero exit on any failure
#
# Speed is measured on the chip by bench/ (BENCHMARK.json, PERF.md).
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1: pytest =="
# includes tests/test_kernels_gp.py — dependency-free interpret-mode
# parity for every force_kernel dispatch path (tests/test_kernels.py
# skips wholesale when hypothesis is absent, so this is the tier-1
# Pallas-vs-oracle coverage)
python -m pytest -x -q

echo "== tier-2: multi-client contention tests =="
REPRO_CONTENTION=1 python -m pytest -q -m contention \
    tests/test_pipeline.py tests/test_batched_fit.py

echo "== tier-2: chaos fault-injection tests =="
# deterministic seeded fault plans (partition/heal/rebalance/failover);
# fencing invariants must hold under every interleaving — plus the
# transport plane's exactly-once batch replay under injected
# mid-response connection kills
REPRO_CHAOS=1 python -m pytest -q -m chaos \
    tests/test_fencing.py tests/test_transport.py
