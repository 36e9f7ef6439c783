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
# 3. Benchmark gate: BENCH_MODE=sharding must show zero steady-state
#    traces and fsdp per-device storage at most half the replicated
#    (dp-only) footprint.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export XLA_FLAGS=--xla_force_host_platform_device_count=8

python -m pytest tests/test_sharding.py -q -p no:cacheprovider

python ci/check_sharding.py

out=$(BENCH_MODE=sharding BENCH_PLATFORM=cpu python bench.py)
echo "$out"
RECORD="$out" python - <<'EOF'
import json, os
rec = json.loads(os.environ["RECORD"].strip().splitlines()[-1])
assert rec.get("unit") == "us/step", rec
assert rec["traces_added"] == 0, rec
assert rec["param_bytes_per_device_sharded"] * 2 <= \
    rec["param_bytes_per_device_dp"], (
    "fsdp did not shard parameter storage: "
    f"{rec['param_bytes_per_device_sharded']}B/device sharded vs "
    f"{rec['param_bytes_per_device_dp']}B/device replicated")
print(f"sharding bench OK: storage ratio {rec['storage_ratio']}, "
      f"{rec['step_us_dp']} us/step dp vs {rec['step_us_sharded']} "
      f"us/step dp*tp*fsdp, 0 retraces")
EOF
