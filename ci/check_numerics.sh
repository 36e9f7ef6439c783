#!/usr/bin/env bash
# Numerics-tier CI hook (tier-1 safe: CPU backend, 8 virtual devices
# for the sharded-parity case).
#
# 1. Behavioral: the numerics test suite (sentinel row vs numpy
#    oracle, one-device_get drain accounting, anomaly rules, injected
#    NaN -> first-bad-op attribution end to end, run-log resume
#    continuity, sharded sentinel parity, legacy Monitor batched toc
#    and device mode, decode logits guard).
# 2. Runtime gates (ci/check_numerics.py): a NaN seeded into one
#    gradient on-device at step N is detected at step N within one
#    drain interval, attributed to the op fed by the poisoned param,
#    with a durable flight record; the per-step host-sync budget is
#    unchanged with MXNET_NUMERICS=1.
# 3. Benchmark gate: BENCH_MODE=numerics A/B (paired, interleaved
#    arms). Design target is <=3% step-time overhead — that is what
#    the fused row costs where XLA fuses the reductions into the step
#    (TPU); on the CPU runner per-kernel dispatch puts the floor at
#    ~5-8%, so the gate backstops at 15%: real regressions (a
#    reintroduced per-step blocking sync) cost +100% or more and
#    still trip it, while scheduler noise does not.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export XLA_FLAGS=--xla_force_host_platform_device_count=8

python -m pytest tests/test_numerics.py -q -p no:cacheprovider

python ci/check_numerics.py

out=$(BENCH_MODE=numerics BENCH_PLATFORM=cpu python bench.py)
echo "$out"
RECORD="$out" python - <<'EOF'
import json, os
rec = json.loads(os.environ["RECORD"].strip().splitlines()[-1])
assert rec.get("unit") == "us/step", rec
assert rec["rows_drained"] > 0, "sentinel drained no rows"
assert rec["overhead_pct"] <= 15.0, (
    "numerics sentinel overhead regressed: "
    f"{rec['overhead_pct']}% of step time (CPU backstop 15%, design "
    f"target {rec['target_pct']}%) — check for a blocking fetch on "
    "the hot path (drain_sentinel must stay non-blocking per step)")
print(f"numerics bench OK: {rec['overhead_pct']}% overhead "
      f"({rec['step_us_off']} us/step off vs {rec['step_us_on']} "
      f"us/step on, interval {rec['interval']}, "
      f"{rec['rows_drained']} rows drained)")
EOF
