#!/usr/bin/env bash
# Elastic-training CI hook (tier-1 safe: CPU backend, local sockets
# and subprocesses only).
#
# 1. Behavioral: tests/test_elastic.py — reshard placement/interval/
#    move math, mid-epoch sampler re-keys (union-of-shards ==
#    uninterrupted remainder, bitwise), slice-decomposable ElasticSGD,
#    the wire codec, the pinned elasticStats surface, and in-process
#    end-to-end shrink/grow bit-identity. Plus the SIGKILL fault-mode
#    unit tests in tests/test_fault.py.
# 2. Runtime gates (ci/check_elastic.py): REAL subprocess workers —
#    one SIGKILLed mid-epoch by its own fault injector (rc -9, no
#    Python teardown), the survivor finishing with final params
#    bitwise equal to an uninterrupted reference and every example
#    consumed exactly once (consumed-log audit vs the Philox ground
#    truth); then a 1→2 re-grow mid-run at zero example loss and zero
#    steady-state retraces.
#
# test_shrink_and_grow_bitwise_identical holds the counts: one
# transition each way, a placement delta that is not empty and beats
# the restore-everyone baseline, zero digest mismatches.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

python -m pytest tests/test_elastic.py tests/test_fault.py -q \
    -p no:cacheprovider

python ci/check_elastic.py
