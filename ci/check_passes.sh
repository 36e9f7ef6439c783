#!/usr/bin/env bash
# Graph-pass-pipeline CI hook (tier-1 safe: CPU backend).
#
# 1. Behavioral: the passes test suite (per-pass numerical parity
#    fwd+bwd, idempotence, env bypass, verifier-on-every-pass-output,
#    cost model + autotuner persistence).
# 2. Runtime A/B gate: a seeded redundant graph binds with the pipeline
#    off and on — fewer executed nodes, 1e-6 parity, zero steady-state
#    retraces, and isomorphic builds converging on one program.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

python -m pytest tests/test_passes.py -q -p no:cacheprovider
python ci/check_passes.py
