#!/usr/bin/env bash
# Device-side observability CI hook (tier-1 safe: CPU backend).
#
# 1. Behavioral: the profiling test suite (instrumented-jit capture +
#    fallbacks, HBM pre-flight warn/strict/attribution, calibration
#    store persistence + calibrated_cost preference order, timeline
#    aggregation, multi-file device-event merge).
# 2. Runtime gate: serving + decode warmups with profiling on —
#    deviceStats covers every cached executable, steady-state traffic
#    adds zero traces and zero records, calibrated_cost is
#    measured-backed for served graphs, and an over-cap bind warns
#    (or raises, strict) BEFORE any trace.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

python -m pytest tests/test_profiling.py -q -p no:cacheprovider
python ci/check_profiling.py
