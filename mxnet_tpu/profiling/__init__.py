"""mxnet_tpu.profiling — device-side observability.

PR 7's telemetry layer sees the host (metrics, spans, endpoints); this
package sees the DEVICE. Three capabilities, each feeding the central
telemetry registry so /metrics, /statusz, and dump_profile expose them
with zero extra wiring:

  executable accounting (device_stats)
      Every jit built through the framework's chokepoints — the exec
      cache's per-mode programs, `sharding.lower.jit_sharded`, the
      decode engine's prefill/decode grid — is wrapped in an
      `InstrumentedJit` that compiles ahead-of-time on first call per
      input signature, captures `compiled.memory_analysis()` (argument
      / output / temp / generated-code bytes) + `cost_analysis()`
      (flops, bytes accessed) + wall trace/compile time, and then
      dispatches through the captured executable (ONE compile — the
      record is free). Records key on canonical digest + kind;
      `deviceStats` is the registry view.

  HBM pre-flight (preflight)
      Before a bind traces anything, estimate params + grads + opt
      state + activations against the device memory cap and emit a
      structured `HBMPreflightWarning` (or raise under
      MXNET_PROFILING_HBM_STRICT=1) with parameter-level attribution —
      the "will this fit?" answer BEFORE the OOM, not after.

  measured-cost calibration (calibration)
      `CalibrationStore` persists (canonical digest, platform, kind) →
      measured seconds, harvested automatically during serving /
      decoding warmup and `fit` epochs (the background refinement
      ROADMAP item 2 asks for). `passes.cost_model.calibrated_cost`
      blends it with the analytic model: measured wins when present,
      analytic otherwise (the Kaufman-et-al. learned-model recipe,
      PAPERS.md, reduced to its lookup table).

Plus `timeline`: the op-level device-time aggregator that attributes
a profiler capture's device operations back to named scopes — graph
nodes (the executor wraps every op in `jax.named_scope(node_name)`)
and the decode tier's layers and parts — through the scope map each
record keeps of its compiled module (`scope_map(module_name)`).

Everything is on by default and CPU-safe; MXNET_PROFILING=0 restores
raw jit dispatch everywhere.
"""
from __future__ import annotations

from .calibration import CalibrationStore, calibration_store
from .device_stats import (InstrumentedJit, device_stats, instrument,
                           profiling_enabled, records_for,
                           reset_device_stats, scope_map)
from .preflight import (HBMPreflightError, HBMPreflightWarning,
                        last_preflight, preflight_bind)
from .timeline import (aggregate_device_events, device_slices,
                       ingest_device_events, read_xplane,
                       timeline_stats)

__all__ = [
    "CalibrationStore", "calibration_store",
    "InstrumentedJit", "device_stats", "instrument",
    "profiling_enabled", "records_for", "reset_device_stats",
    "HBMPreflightError", "HBMPreflightWarning",
    "last_preflight", "preflight_bind",
    "aggregate_device_events", "device_slices",
    "ingest_device_events", "read_xplane", "scope_map",
    "timeline_stats",
]
