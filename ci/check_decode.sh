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
# 3. Benchmark gate: BENCH_MODE=decode must show zero steady-state
#    traces, paged-KV padding waste strictly below the one-shot
#    batcher's rectangular cache, prefix reuse, and speculative
#    speedup on its shared-prefix workload.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

python -m pytest tests/test_decoding.py tests/test_decode_prefix_spec.py \
    -q -p no:cacheprovider

python ci/check_decode.py

out=$(BENCH_MODE=decode BENCH_PLATFORM=cpu python bench.py)
echo "$out"
RECORD="$out" python - <<'EOF'
import json, os
rec = json.loads(os.environ["RECORD"].strip().splitlines()[-1])
assert rec.get("unit") == "tok/s", rec
assert rec["traces_added"] == 0, rec
assert rec["traces_since_warmup"] == 0, rec
assert rec["padding_waste_paged"] < rec["padding_waste_oneshot"], (
    "paged KV cache wastes more memory than the rectangular layout: "
    f"{rec['padding_waste_paged']} vs {rec['padding_waste_oneshot']}")
print(f"decode bench OK: {rec['decode_tokens_per_s']} decode tok/s, "
      f"{rec['prefill_tokens_per_s']} prefill tok/s, paged waste "
      f"{rec['padding_waste_paged']} vs one-shot "
      f"{rec['padding_waste_oneshot']}, 0 retraces")
EOF
