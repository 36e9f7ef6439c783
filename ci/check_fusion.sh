#!/usr/bin/env bash
# Pallas-codegen CI hook (tier-1 safe: CPU backend, interpret-mode
# kernels).
#
# 1. Behavioral: the codegen test suite (per-template interpret parity
#    fwd+bwd through the fused executor, counted fallbacks, exec-cache
#    key separation, ragged mixed-batch kernel vs dense oracle,
#    merged-step trace-grid pin).
# 2. Runtime gate: every marked fusion group lowers with a parity
#    proof or carries a counted fallback reason (no silent drops),
#    kind="kernel" calibration records exist, and the merged ragged
#    step shrinks the warmup grid at zero retraces with
#    token-identical output.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

python -m pytest tests/test_pallas_codegen.py -q -p no:cacheprovider
python ci/check_fusion.py
