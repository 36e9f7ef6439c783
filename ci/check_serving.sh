#!/usr/bin/env bash
# Serving-tier CI hook (tier-1 safe: CPU backend).
#
# 1. Behavioral: the serving test suite (bucketing/padding round-trip,
#    flush policy, backpressure, deadlines, multi-model isolation,
#    zero-retrace steady state).
# 2. Benchmark gate: BENCH_MODE=serving must show dynamic batching
#    beating a pre-warmed single-request Predictor loop >= 2x, with
#    ZERO compiled-program traces added in steady state.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

python -m pytest tests/test_serving.py -q -p no:cacheprovider

out=$(BENCH_MODE=serving BENCH_PLATFORM=cpu python bench.py)
echo "$out"
RECORD="$out" python - <<'EOF'
import json, os
rec = json.loads(os.environ["RECORD"].strip().splitlines()[-1])
assert rec.get("unit") == "req/s", rec
assert rec["vs_single"] >= 2.0, (
    f"dynamic batching speedup {rec['vs_single']}x < 2x")
assert rec["traces_added"] == 0, rec
assert rec["traces_since_warmup"] == 0, rec
print(f"serving-check OK: {rec['value']} req/s, "
      f"{rec['vs_single']}x vs single-request, 0 retraces")
EOF
