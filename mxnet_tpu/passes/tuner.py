"""Autotuner: per-(canonical graph, platform) tuning choices.

Picks the three knobs the rest of the stack already understands —
`layout` (the opt-in layout pass), `multistep_k` (steps fused per
dispatch, module/executor_group multistep), `bucket_grid` (the
(batch,) padding grid the serving tier warms) — analytic-first from
`cost_model`, optionally refined by an on-device measurement
(`measure=True` binds the graph and times real forwards).

Choices persist as JSON at MXNET_TUNING_CACHE (default
~/.cache/mxnet_tpu/tuning.json) keyed by `"{canonical_digest}:
{platform}"`, so a graph tuned once is tuned forever: the digest is
the canonical-pipeline signature, meaning every differently-built
isomorphic variant of a network maps to the one cached record.
"""
from __future__ import annotations

import json
import os
import threading
import time

# fused-multistep dispatch window the measured refinement targets: big
# enough to amortize host dispatch, small enough to keep host metrics
# fresh (~one progress-bar tick)
_TARGET_WINDOW_S = 2e-3
_MULTISTEP_CHOICES = (1, 2, 4, 8, 16, 32)


def _default_cache_path():
    from ..utils import getenv

    return os.path.expanduser(str(getenv("MXNET_TUNING_CACHE")))


def _pow2_grid(n):
    """Powers of two up to and including the first >= n."""
    out = [1]
    while out[-1] < int(n):
        out.append(out[-1] * 2)
    return out


class Autotuner:
    """choose() -> {"layout", "multistep_k", "bucket_grid"} for a
    (symbol, shapes, platform), cached across processes."""

    def __init__(self, cache_path=None):
        self.cache_path = cache_path or _default_cache_path()
        self._lock = threading.Lock()
        # records chosen by this process; every save persists the full
        # set, so a lost disk write is healed by the next one
        self._local = {}

    # ------------------------------------------------------ persistence
    def _load(self):
        try:
            with open(self.cache_path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}

    def _save(self, table):
        from ..utils.persist import atomic_write_json

        # atomic vs concurrent tuners (tmp + fsync + os.replace)
        atomic_write_json(self.cache_path, table)

    # ----------------------------------------------------------- choice
    def choose(self, symbol, input_shapes, platform=None, measure=False):
        """Tuning record for `symbol` at `input_shapes` on `platform`
        (default: the active jax backend). Cached records win; a
        `measure=True` record wins over a cached analytic one."""
        from . import cost_model as _cm

        if platform is None:
            import jax

            platform = jax.default_backend()
        digest = symbol.canonical_signature()
        key = f"{digest}:{platform}"
        # disk I/O happens OUTSIDE self._lock: _load is a read of an
        # atomically-replaced file and needs no exclusion, and holding
        # a lock across filesystem latency stalls every other tuning
        # thread. The lock guards only the in-memory merge below.
        cached = self._load().get(key)
        if cached is None:
            with self._lock:
                cached = self._local.get(key)
        if cached is not None and cached.get("source") == "measured":
            return cached
        # calibration upgrade: a measured forward time harvested into
        # the CalibrationStore (profiling) refines an analytic record
        # for free — no on-device measurement run needed here
        calibrated_s = _calibration_forward_s(digest, platform)
        if cached is not None and not measure:
            if (calibrated_s is not None
                    and cached.get("source") == "analytic"):
                record = dict(cached)
                record["multistep_k"] = _k_for_window(calibrated_s)
                record["measured_forward_s"] = calibrated_s
                record["source"] = "calibrated"
                self._persist(key, record)
                return record
            return cached

        shapes = {k: tuple(v) for k, v in input_shapes.items()}
        record = {
            "layout": _cm.choose_layout(symbol, shapes, platform),
            "multistep_k": self._analytic_multistep(
                symbol, shapes, platform),
            "bucket_grid": _pow2_grid(self._batch_of(shapes)),
            "platform": platform,
            "source": "analytic",
        }
        if measure:
            step_s = _measured_forward_s(symbol, shapes)
            if step_s is not None:
                record["multistep_k"] = _k_for_window(step_s)
                record["measured_forward_s"] = step_s
                record["source"] = "measured"
        elif calibrated_s is not None:
            record["multistep_k"] = _k_for_window(calibrated_s)
            record["measured_forward_s"] = calibrated_s
            record["source"] = "calibrated"
        self._persist(key, record)
        return record

    def _persist(self, key, record):
        """Adopt `record` locally and best-effort save: merge this
        process's full record set over the current disk table and
        replace atomically. A concurrent external writer can win the
        race for one save, but the next save here re-merges
        everything in _local, so a lost record only costs a re-tune."""
        with self._lock:
            self._local[key] = record
            pending = dict(self._local)
        # disk merge OUTSIDE the lock (MX006: no I/O under locks)
        table = self._load()
        table.update(pending)
        try:
            self._save(table)
        except OSError:
            pass  # read-only cache dir: tuning still works, unpersisted

    @staticmethod
    def _batch_of(shapes):
        for s in shapes.values():
            if s:
                return max(int(s[0]), 1)
        return 1

    @staticmethod
    def _analytic_multistep(symbol, shapes, platform):
        """Steps per fused dispatch from the byte model
        (cost_model.analytic_step_s): fuse enough steps to fill the
        dispatch window. CPU keeps k=1 (dispatch is cheap,
        debuggability wins)."""
        if platform == "cpu":
            return 1
        from . import cost_model as _cm

        return _k_for_window(
            _cm.analytic_step_s(symbol, shapes, platform))


def _calibration_forward_s(digest, platform):
    """Measured forward seconds for (digest, platform) from the
    profiling CalibrationStore, or None (store missing/empty — the
    pre-calibration behavior is exactly the old analytic path)."""
    try:
        from ..profiling import calibration_store

        return calibration_store().measured_seconds(
            digest, platform, "forward")
    except Exception:
        return None


def _k_for_window(step_s):
    k = 1
    for cand in _MULTISTEP_CHOICES:
        if cand * step_s <= _TARGET_WINDOW_S:
            k = cand
    return k


def _measured_forward_s(symbol, input_shapes, repeats=5):
    """Median wall time of a real bound forward (the on-device
    refinement). Returns None when the symbol cannot be bound at these
    shapes (missing shapes, unsupported backend)."""
    try:
        from ..context import cpu, current_context

        try:
            ctx = current_context()
        except Exception:
            ctx = cpu()
        exe = symbol.simple_bind(ctx=ctx, grad_req="null",
                                 **input_shapes)
        exe.forward(is_train=False)[0].asnumpy()  # compile + settle
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            exe.forward(is_train=False)[0].asnumpy()
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2]
    except Exception:
        return None
