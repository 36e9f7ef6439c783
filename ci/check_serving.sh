#!/usr/bin/env bash
# Serving-tier CI hook (tier-1 safe: CPU backend).
#
# Behavioral: the serving test suite (bucketing/padding round-trip,
# flush policy, backpressure, deadlines, multi-model isolation, and
# ZERO compiled-program traces added in steady state:
# test_steady_state_serving_adds_zero_traces).
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

python -m pytest tests/test_serving.py -q -p no:cacheprovider
