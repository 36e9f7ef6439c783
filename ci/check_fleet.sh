#!/usr/bin/env bash
# Fleet control-plane CI hook (tier-1 safe: CPU backend, local
# sockets only).
#
# 1. Behavioral: tests/test_fleet.py — prefix digests and the
#    affinity index, autoscaler hysteresis, drain ledger, wire
#    framing, router routing/death-rebuild/staleness/deadline paths
#    against fake replicas, the admin protocol + CLI, and the real
#    in-process drain-handoff bit-identity suite.
# 2. Runtime gates (ci/check_fleet.py): a 3-replica fleet of REAL
#    subprocesses off one shared bundle — every replica (and the
#    healed replacement) restores with 0 traces / 0 compiles;
#    SIGKILL mid-stream and graceful drain both finish every request
#    with zero failures and token streams bit-identical to an
#    uninterrupted single-process reference.
#
# test_affinity_routing_beats_random_on_hits_and_pages holds the
# routing A/B: affinity must strictly win on fleet-wide prefix hit
# rate AND on total KV pages allocated for the same traffic.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
# replicas must restore from the bundle alone, not an ambient disk
# exec cache
export MXNET_EXEC_CACHE_DIR=

python -m pytest tests/test_fleet.py -q -p no:cacheprovider

python ci/check_fleet.py
