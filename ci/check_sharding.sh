#!/usr/bin/env bash
# Sharding-tier CI hook (tier-1 safe: CPU backend with 8 virtual
# devices).
#
# 1. Behavioral: the sharding test suite (rule-table precedence and
#    round-trips, advisory downgrades vs explicit rejection, plan
#    digest / exec-cache keying, dp / dp*tp*fsdp training parity,
#    fsdp storage, kvstore mesh barrier + replicated pinning).
# 2. Runtime gates (ci/check_sharding.py): bitwise np.array_equal
#    parity across unsharded / {'data':8} / {'data':2,'fsdp':2,'tp':2}
#    on exact arithmetic; per-device param bytes <= 1/2 replicated;
#    zero steady-state retraces; pre-trace rejection of a non-dividing
#    explicit spec, naming parameter/axis/sizes.
#
# The suite holds the counts as well: no trace after the first steps
# under a replicated and a partitioned plan
# (test_sharded_steady_state_adds_no_trace), fsdp per-device storage
# at most half the replicated footprint
# (test_dp_tp_fsdp_parity_and_storage).
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export XLA_FLAGS=--xla_force_host_platform_device_count=8

python -m pytest tests/test_sharding.py -q -p no:cacheprovider

python ci/check_sharding.py
