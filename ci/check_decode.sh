#!/usr/bin/env bash
# Decode-tier CI hook (tier-1 safe: CPU backend).
#
# 1. Behavioral: the decoding test suites (allocator invariants, COW
#    fork, kernel parity, continuous-batching parity, preempt/readmit
#    bit-identity, per-step deadlines, streaming, stats pinning; plus
#    prefix-cache radix/churn, sampling reproducibility, speculative
#    parity, and stream-cancellation coverage).
# 2. Runtime gates (ci/check_decode.py): zero retraces over a >=64-step
#    continuous decode with mid-stream admission/eviction/preemption;
#    greedy parity vs an unbatched reference; pool exhaustion preempts
#    instead of crashing; shared-prefix workloads reuse >=50% of
#    prompt pages with a falling allocation count; K=4 self-draft
#    speculative decoding token-identical to target-only at >1.5
#    accepted tokens/target step; sampled output bit-identical across
#    preemption.
#
# The suites hold the counts as well: zero traces since warm-up, and
# the paged cache's reserved slots under a rectangular cache's
# (test_paged_cache_reserves_less_than_a_rectangular_one).
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

python -m pytest tests/test_decoding.py tests/test_decode_prefix_spec.py \
    -q -p no:cacheprovider

python ci/check_decode.py
