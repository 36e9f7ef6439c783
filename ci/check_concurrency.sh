#!/usr/bin/env bash
# Concurrency race gate (docs/analysis.md, Concurrency rules). Four
# checks, static and dynamic halves each proven both ways:
#
# 1. The tree is clean: the project-scope concurrency pass
#    (MX006 blocking-under-lock, MX007 lock-order inversion, MX008
#    unlocked shared write) reports ZERO findings with NO baseline —
#    the no-grandfathering bar of the lint gate, applied to locks.
# 2. The static gate gates: a seeded two-lock inversion in a scratch
#    file must be flagged as MX007 (guards against an engine that
#    silently stops seeing cycles).
# 3. The runtime witness gates: the same inversion executed live under
#    MXNET_LOCK_WITNESS=raise must raise LockOrderViolation at the
#    acquisition attempt that completes the cycle — the deadlock
#    becomes a diagnosed exception, in a bounded amount of time.
# 4. The soak: serving + decoding + DataLoader + telemetry exporter
#    run concurrently under the witness and must finish deadlock-free
#    with no witnessed cycle; the dynamic held-before graph is
#    cross-checked against the static one.
#
# Checks 1-3 are stdlib-only (mxlint + lockwitness never import jax);
# the soak needs the CPU backend (the Makefile target sets
# JAX_PLATFORMS=cpu).
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== concurrency: full tree, MX006-MX008, no baseline"
python tools/mxlint.py mxnet_tpu tools examples \
    --select MX006,MX007,MX008 --no-baseline

scratch="$(mktemp -d)"
trap 'rm -rf "$scratch"' EXIT

echo "== concurrency: seeded inversion must be flagged statically"
cat > "$scratch/seeded.py" <<'EOF'
import threading


class Inverted:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def forward(self):
        with self._a:
            with self._b:
                pass

    def reverse(self):
        with self._b:
            with self._a:
                pass
EOF
if python tools/mxlint.py "$scratch" --no-baseline \
        --select MX007 > "$scratch/out.txt"; then
    echo "FAIL: static pass did not flag the seeded inversion" >&2
    cat "$scratch/out.txt" >&2
    exit 1
fi
grep -q "MX007" "$scratch/out.txt" \
    || { echo "FAIL: non-MX007 failure:" >&2; cat "$scratch/out.txt" >&2; exit 1; }
echo "ok: seeded inversion flagged (MX007)"

echo "== concurrency: seeded inversion must be caught by the witness"
python - <<'EOF'
import sys, threading, time
sys.path.insert(0, "mxnet_tpu/analysis")
import lockwitness

lockwitness.install("raise")
# one constructor per line: a lock's witness identity is its creation
# site, and same-site pairs are exempt (cross-instance false positives)
l1 = threading.Lock()
l2 = threading.Lock()
caught = []


def forward():
    try:
        with l1:
            time.sleep(0.05)
            with l2:
                pass
    except lockwitness.LockOrderViolation as e:
        caught.append(e)


def reverse():
    time.sleep(0.02)
    try:
        with l2:
            with l1:
                pass
    except lockwitness.LockOrderViolation as e:
        caught.append(e)


a = threading.Thread(target=forward, daemon=True)
b = threading.Thread(target=reverse, daemon=True)
a.start(); b.start(); a.join(30); b.join(30)
assert not a.is_alive() and not b.is_alive(), \
    "witness failed: the inversion deadlocked instead of raising"
assert caught, "witness failed: no LockOrderViolation raised"
assert lockwitness.violations(), "witness recorded no cycle"
print("ok: witness raised", type(caught[0]).__name__,
      "instead of deadlocking")
EOF

echo "== concurrency: multi-subsystem soak under the witness"
python ci/check_concurrency_soak.py

echo "race-check OK"
