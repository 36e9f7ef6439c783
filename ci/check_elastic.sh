#!/usr/bin/env bash
# Elastic-training CI hook (tier-1 safe: CPU backend, local sockets
# and subprocesses only).
#
# 1. Behavioral: tests/test_elastic.py — reshard placement/interval/
#    move math, mid-epoch sampler re-keys (union-of-shards ==
#    uninterrupted remainder, bitwise), slice-decomposable ElasticSGD,
#    the wire codec, the pinned elasticStats surface, and in-process
#    end-to-end shrink/grow bit-identity. Plus the SIGKILL fault-mode
#    unit tests in tests/test_fault.py.
# 2. Runtime gates (ci/check_elastic.py): REAL subprocess workers —
#    one SIGKILLed mid-epoch by its own fault injector (rc -9, no
#    Python teardown), the survivor finishing with final params
#    bitwise equal to an uninterrupted reference and every example
#    consumed exactly once (consumed-log audit vs the Philox ground
#    truth); then a 1→2 re-grow mid-run at zero example loss and zero
#    steady-state retraces.
# 3. Benchmark gate: BENCH_MODE=elastic — a shrink + grow mid-run;
#    the placement delta must beat the restore-everyone baseline and
#    both transitions must leave zero digest mismatches.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

python -m pytest tests/test_elastic.py tests/test_fault.py -q \
    -p no:cacheprovider

python ci/check_elastic.py

out=$(BENCH_MODE=elastic BENCH_PLATFORM=cpu python bench.py)
echo "$out"
RECORD="$out" python - <<'EOF'
import json, os
rec = json.loads(os.environ["RECORD"].strip().splitlines()[-1])
assert rec.get("unit") == "steps_per_s", rec
assert rec["elastic_transitions"] == 2, rec["elastic_transitions"]
moved, full = rec["elastic_reshard_bytes_moved"], \
    rec["elastic_reshard_bytes_full_restore"]
assert 0 < moved < full, (
    f"placement delta does not beat the full-restore baseline: "
    f"{moved} vs {full}")
assert rec["elastic_digest_mismatches"] == 0, (
    f"bitwise drift across transitions: "
    f"{rec['elastic_digest_mismatches']} digest mismatches")
print(f"elastic bench OK: {rec['elastic_steps_per_s']} steps/s "
      f"across 2 transitions, quiesce "
      f"{rec['elastic_quiesce_wall_ms']} ms, reshard {moved} B vs "
      f"{full} B full restore ({rec['elastic_reshard_savings']}x)")
EOF
