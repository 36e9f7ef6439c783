#!/usr/bin/env bash
# Executor-cache CI hook (tier-1 safe: CPU backend).
#
# 1. Static guard: no jax.jit constructed inside per-step code paths —
#    retracing there would defeat the cache's dispatch amortization.
# 2. Behavioral: the exec_cache test suite (rebind sharing, bucketing
#    revisits, key discrimination, LRU eviction).
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

python ci/check_no_perstep_jit.py
python -m pytest tests/test_exec_cache.py -q -p no:cacheprovider
