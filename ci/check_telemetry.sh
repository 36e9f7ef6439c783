#!/usr/bin/env bash
# Telemetry-tier CI hook (tier-1 safe: CPU backend).
#
# 1. Behavioral: the telemetry test suite (registry instruments +
#    Prometheus rendering, span ring + correlation, serving/fit span
#    paths, exporter endpoints, dump_profile key-shape compatibility,
#    flight recorder).
# 2. Runtime gates (ci/check_telemetry.py): every request correlated
#    submit->reply, /metrics + /statusz parse AND agree with the
#    in-process snapshots, always-on tracing within 3% of step time,
#    and a FaultInjector trip leaves a flight record on disk.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

python -m pytest tests/test_telemetry.py -q -p no:cacheprovider
python ci/check_telemetry.py
