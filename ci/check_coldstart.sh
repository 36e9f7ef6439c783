#!/usr/bin/env bash
# Cold-start CI hook (tier-1 safe: CPU backend).
#
# 1. Behavioral: the disk exec-cache + bundle test suite (restart
#    restores with zero traces/compiles, stale-version fallback
#    re-traces, corrupt artifacts quarantined not fatal, LRU size-cap
#    eviction, bundle tamper rejection, calibration-skip counting).
# 2. Runtime gate: three real subprocesses against one bundle — warm
#    snapshot, zero-trace/zero-compile restore with bit-identical
#    outputs, tampered-bundle rejection (ci/check_coldstart.py).
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

python -m pytest tests/test_disk_cache.py -q -p no:cacheprovider
python ci/check_coldstart.py
