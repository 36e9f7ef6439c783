#!/usr/bin/env bash
# Quantized-serving CI hook (tier-1 safe: CPU backend).
#
# 1. Behavioral: tests/test_quant.py — quantize/dequantize round-trip
#    vs a numpy oracle, COW scale-plane churn soak, speculative int8
#    exact parity, dtype-salted prefix digests, weight-only bundle
#    round-trip + precision-mismatch refusal.
# 2. Runtime gates (ci/check_quant.py): int8 greedy top-1 agreement
#    >= 0.9 vs float32 on the CI decoder; measured pool capacity
#    >= 1.9x; zero steady-state retraces under int8 traffic; a
#    quantize="int8" bundle restores in a FRESH process at 0 traces /
#    0 compiles with an identical token stream; a stripped
#    quantization record is refused.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

python -m pytest tests/test_quant.py -q -p no:cacheprovider

python ci/check_quant.py
