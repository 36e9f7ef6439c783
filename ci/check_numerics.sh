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
#
# What the sentinel costs in step time is a speed: not gated here (a
# reintroduced per-step blocking sync trips the host-sync budget of
# gate 2).
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export XLA_FLAGS=--xla_force_host_platform_device_count=8

python -m pytest tests/test_numerics.py -q -p no:cacheprovider

python ci/check_numerics.py
