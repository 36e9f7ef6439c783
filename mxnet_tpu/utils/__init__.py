"""Utilities: the runtime config/flag system.

The reference reads ~25 MXNET_* env vars via dmlc::GetEnv at point of
use (docs/how_to/env_var.md; SURVEY.md §5 config tiers). Here every
supported variable is declared in one registry with type, default, and
help, read through typed getters — `mxnet_tpu.utils.getenv(name)` —
so `describe_env()` prints the live configuration (the env_var.md
analog, generated instead of hand-written).
"""
from __future__ import annotations

import os
from dataclasses import dataclass

from ..base import MXNetError


@dataclass
class EnvVar:
    name: str
    type: type
    default: object
    help: str


_ENV_REGISTRY: dict[str, EnvVar] = {}


def register_env(name, type_, default, help_):
    _ENV_REGISTRY[name] = EnvVar(name, type_, default, help_)


def getenv(name):
    """Typed read of a registered MXNET_* variable."""
    if name not in _ENV_REGISTRY:
        raise MXNetError(f"unknown env var {name!r}")
    spec = _ENV_REGISTRY[name]
    raw = os.environ.get(name)
    if raw is None:
        return spec.default
    if spec.type is bool:
        return raw not in ("0", "false", "False", "")
    return spec.type(raw)


def pallas_interpret():
    """Whether a Pallas kernel runs in interpret mode — the ONE place
    that decides. Off the TPU it must (Mosaic compiles for TPUs only).
    On a TPU a kernel is compiled or the call raises."""
    import jax

    return jax.default_backend() != "tpu"


def describe_env():
    """All registered vars with current values (env_var.md analog)."""
    lines = []
    for spec in sorted(_ENV_REGISTRY.values(), key=lambda s: s.name):
        cur = getenv(spec.name)
        lines.append(
            f"{spec.name}={cur!r} (default {spec.default!r}) — "
            f"{spec.help}"
        )
    return "\n".join(lines)


# ---- the supported surface (reference docs/how_to/env_var.md) ----
register_env(
    "MXNET_ENGINE_TYPE", str, "ThreadedEngine",
    "host-side engine implementation: ThreadedEngine | NaiveEngine "
    "(reference src/engine/engine.cc:14)",
)
register_env(
    "MXNET_CPU_WORKER_NTHREADS", int, 4,
    "worker threads of the host engine / data pipeline "
    "(reference env_var.md)",
)
register_env(
    "MXNET_TPU_OPT_STATE_DTYPE", str, "",
    "dtype for optimizer state (momentum/moments) in the fused train "
    "step, e.g. 'bfloat16': halves optimizer-update HBM traffic; "
    "update math still runs in f32 and rounds back on store "
    "(parallel/dp_step.py). Empty = weight dtype.",
)
register_env(
    "MXNET_TPU_OPT_BUCKET", bool, False,
    "flat-bucket optimizer update in the fused train step: ONE "
    "apply_dense over all trainable params concatenated (multi-tensor "
    "apply) instead of one per parameter; auto-disabled for sharded/"
    "mixed-dtype params (parallel/dp_step.py _bucket_plan).",
)
register_env(
    "MXNET_TPU_BUCKET_FUSED", bool, False,
    "fused train steps for BucketingModule: each bucket compiles its "
    "own donated step and the canonical training state hands over on "
    "bucket switch (module/bucketing_module.py _ensure_owner); "
    "default keeps the reference's shared-NDArray eager updates.",
)
register_env(
    "MXNET_TPU_COORDINATOR", str, "",
    "jax.distributed coordinator address (set by tools/launch.py)",
)
register_env(
    "MXNET_TPU_MEM_FRACTION", str, "",
    "HBM pool fraction for the XLA client (pooled-storage-manager "
    "knob analog; applied at import if the backend is uninitialized)",
)
register_env(
    "MXNET_TPU_NUM_WORKERS", int, 1,
    "worker process count (set by tools/launch.py)",
)
register_env(
    "MXNET_TPU_WORKER_ID", int, 0,
    "this process's worker id (set by tools/launch.py)",
)
register_env(
    "MXNET_TPU_XLA_TRACE_DIR", str, "",
    "when set, profiler_set_state('run') also captures an XLA device "
    "trace via jax.profiler into this directory",
)
register_env(
    "MXNET_BACKWARD_DO_MIRROR", bool, False,
    "rematerialize forward activations during backward "
    "(jax.checkpoint) — the reference's memory-mirror/memonger "
    "(README.md:352-359): ~10% slower, much less activation memory",
)
register_env(
    "MXNET_EXEC_CACHE", bool, True,
    "process-wide compiled-computation cache (exec_cache, the CachedOp "
    "analog): executors bound to the same graph signature + shapes "
    "share one traced program. 0 disables sharing — every bind builds "
    "a private program (docs/faq.md).",
)
register_env(
    "MXNET_SERVING_MAX_BATCH", int, 8,
    "serving: largest batch bucket of the dynamic batcher — one "
    "compiled program per (batch, length) bucket; a bucket group "
    "flushes the moment it reaches this size (mxnet_tpu.serving).",
)
register_env(
    "MXNET_SERVING_MAX_WAIT_US", int, 2000,
    "serving: max microseconds a partial batch waits for co-riders "
    "before flushing — the latency bound of the batching tradeoff.",
)
register_env(
    "MXNET_SERVING_QUEUE_CAP", int, 256,
    "serving: bounded request-queue admission limit per model; a full "
    "queue fast-fails submits with ServerBusyError (backpressure) "
    "instead of buffering unboundedly.",
)
register_env(
    "MXNET_SERVING_BUCKETS", str, "",
    "serving: comma-separated batch buckets (e.g. '1,2,4,8') "
    "overriding the powers-of-two default grid up to MAX_BATCH.",
)
register_env(
    "MXNET_SERVING_LENGTH_BUCKETS", str, "",
    "serving: comma-separated ragged-axis buckets (e.g. '16,32,64') "
    "for models whose input_specs declare an 'L' axis; requests pad "
    "up to the nearest bucket (docs/serving.md).",
)
register_env(
    "MXNET_DISPATCH_AHEAD", int, 2,
    "max in-flight training steps the fit loop keeps dispatched ahead "
    "of the device (module/base_module.py): batch N+1 is staged while "
    "step N runs. Each in-flight step holds its batch + activations in "
    "HBM — lower it if training OOMs; 0 blocks on every step "
    "(synchronous, the pre-pipelined behavior).",
)
register_env(
    "MXNET_DEVICE_METRICS", bool, True,
    "accumulate EvalMetric sums/counts as device scalars, fetched only "
    "when get() runs (log intervals + epoch end) instead of one "
    "blocking asnumpy per batch (metric.py update_device). 0 forces "
    "the host update() path for every metric.",
)
register_env(
    "MXNET_DATA_WORKERS", int, 2,
    "data: producer threads per DataLoader decoding batches into "
    "bounded per-worker queues (mxnet_tpu.data). Batch order is "
    "deterministic for ANY worker count — batch k always comes from "
    "worker k % MXNET_DATA_WORKERS.",
)
register_env(
    "MXNET_DATA_QUEUE_CAP", int, 4,
    "data: max decoded batches each loader worker buffers; a producer "
    "that runs ahead blocks (backpressure bounds host RAM no matter "
    "how slow the consumer is).",
)
register_env(
    "MXNET_DATA_DEVICE_PREFETCH", int, 2,
    "data: batches DevicePrefetchIter keeps device-resident ahead of "
    "the step (async device_put; 2 = double-buffered). 0 = synchronous "
    "host->device copy inline in next() — every batch then counts as "
    "an input stall (ci/check_input_stall.py's A/B arm).",
)
register_env(
    "MXNET_DATA_SEED", int, 0,
    "data: default shuffle seed of ShardedSampler/DataLoader. The "
    "epoch permutation is a pure function of (seed, epoch), so every "
    "host derives the same global order with zero coordination and "
    "resume replays the identical stream (docs/data.md).",
)
register_env(
    "MXNET_EXEC_CACHE_SIZE", int, 64,
    "LRU bound on retained exec_cache entries; raise it when cycling "
    "more distinct bucket/shape signatures than this. Stats: "
    "mxnet_tpu.executor.cache_stats().",
)
register_env(
    "MXNET_GRAPH_VERIFY", bool, False,
    "run the pre-bind graph verifier (mxnet_tpu.analysis.verify_graph) "
    "inside Executor binding: shape/dtype contradictions, duplicate "
    "argument names, and donation-aliasing hazards are reported with "
    "the offending op named, BEFORE jit tracing turns them into an "
    "XLA stack trace. Always on in the test suite (tests/conftest.py); "
    "off by default in production binds (docs/analysis.md).",
)
register_env(
    "MXNET_GRAPH_PASSES", str, "1",
    "graph-optimization pass pipeline run on every bind ahead of the "
    "exec-cache lookup (mxnet_tpu.passes): '1'/'on' = the default "
    "pipeline (dce, fold, cse, canonicalize); '0'/'off' "
    "= trace graphs exactly as constructed; a comma list selects and "
    "orders passes explicitly, e.g. 'dce,fold,cse,layout,"
    "canonicalize' to add the opt-in NCHW->NHWC layout rewrite "
    "(docs/passes.md).",
)
register_env(
    "MXNET_PASS_FOLD_MAX", int, 65536,
    "constant folding's per-tensor element cap (mxnet_tpu.passes): a "
    "const subgraph whose result (or declared shape param) exceeds "
    "this many elements stays in the traced graph instead of being "
    "baked into the serialized form as a _graph_constant.",
)
register_env(
    "MXNET_TUNING_CACHE", str, "~/.cache/mxnet_tpu/tuning.json",
    "autotuner persistence (passes.Autotuner): JSON of tuning choices "
    "(layout / multistep_k / bucket_grid) keyed by canonical graph "
    "digest + platform; delete the file to re-tune from scratch "
    "(docs/passes.md).",
)
register_env(
    "MXNET_TPU_WORKER_ID_FROM_MPI", bool, False,
    "dist bootstrap: derive process_id from OMPI_COMM_WORLD_RANK / "
    "PMI_RANK instead of MXNET_TPU_WORKER_ID when launching under "
    "mpirun/srun (mxnet_tpu._dist_bootstrap).",
)
register_env(
    "MXNET_TPU_FAULT_INJECT", str, "",
    "resilience testing: deterministic crash injection for "
    "fit_auto_resume ('epoch:N' fires after epoch N's checkpoint is "
    "durable; 'step:N' fires at global batch N, the mid-epoch hard "
    "resume case). Fires once, then the resumed run proceeds "
    "(mxnet_tpu.fault.FaultInjector).",
)
register_env(
    "MXNET_TELEMETRY_PORT", str, "",
    "telemetry: set to a TCP port to start the in-process HTTP "
    "exporter (mxnet_tpu.telemetry.http) answering /metrics "
    "(Prometheus text), /statusz (JSON snapshot of every registered "
    "subsystem), and /healthz. Attached by serving.ModelServer and "
    "Module.fit; '0' binds an ephemeral port (the chosen port is in "
    "telemetry.http.exporter_port()). Unset = no server, zero "
    "overhead (docs/observability.md).",
)
register_env(
    "MXNET_TELEMETRY_SPANS", int, 2048,
    "telemetry: capacity of the always-on structured-trace ring "
    "buffer (spans retained for /statusz, flight records, and "
    "spans_for_trace correlation). 0 disables span recording "
    "entirely — record_span returns before constructing the Span "
    "(the overhead A/B arm of ci/check_telemetry.py).",
)
register_env(
    "MXNET_TELEMETRY_FLIGHT_DIR", str, "",
    "telemetry: directory the flight recorder writes crash dumps "
    "into (last-N spans + full metrics/stats snapshot as JSON, "
    "atomic tmp+rename). Dumps fire on unhandled exceptions (sys/"
    "threading excepthook) and on fault.FaultInjector trips. Unset "
    "= flight recording off (docs/observability.md).",
)
register_env(
    "MXNET_DECODE_PAGE_SIZE", int, 16,
    "decoding: tokens per KV-cache page. Smaller pages waste fewer "
    "slots per sequence (worst case page_size-1 tokens) but grow the "
    "page table and the decode-step gather fan-out; 16 matches the "
    "Ragged Paged Attention layout (docs/serving.md).",
)
register_env(
    "MXNET_DECODE_PAGES", int, 64,
    "decoding: total pages in the pre-allocated device KV pool "
    "(page 0 is reserved scratch, so capacity is PAGES-1). The pool "
    "is THE decode memory budget: when it runs out the scheduler "
    "preempts the lowest-priority sequence instead of OOMing.",
)
register_env(
    "MXNET_DECODE_MAX_BATCH", int, 4,
    "decoding: rows in the fixed-shape continuous decode batch. "
    "Every decode step runs at exactly this batch (inactive rows "
    "masked), which is what keeps the step shape grid finite and "
    "fully pre-traceable at warmup.",
)
register_env(
    "MXNET_DECODE_PAGE_BUCKETS", str, "",
    "decoding: comma list of pages-per-sequence buckets (e.g. "
    "'2,4,8'); the decode-step shape is a function only of "
    "(max_batch, bucket), one pre-traced program per bucket. Empty = "
    "powers of two up to the pool-derived per-sequence maximum.",
)
register_env(
    "MXNET_DECODE_KERNEL", str, "",
    "decoding: single-query page-table attention implementation. "
    "Unset, the backend decides: 'pallas' on a TPU, 'lax' elsewhere. "
    "'lax' gathers a row's pages into a context and runs masked "
    "softmax attention over it (runs anywhere; the CPU's form and the "
    "kernel's reference). 'pallas' reads each row's live pages in "
    "place, several pages a block with the next block's copies in "
    "flight, and does no work for pages a row does not own "
    "(interpreted off the TPU).",
)
register_env(
    "MXNET_DECODE_MERGED_STEP", bool, True,
    "decoding: run tail-prefill tokens and decode rows in ONE "
    "fixed-shape ragged step program (the Ragged Paged Attention "
    "unification) instead of separate pre-traced tail-prefill "
    "programs per length bucket — shrinks the warmup trace grid. "
    "Applies when the prefix cache is on and speculative decoding "
    "is off; 0 restores the split prefill/decode grid.",
)
register_env(
    "MXNET_DECODE_KV_DTYPE", str, "float32",
    "decoding: KV page-pool storage precision — float32 (default), "
    "bf16, or int8. int8 stores pages quantized with a per-page "
    "float32 scale plane (per-(slot,head) granularity), quantized at "
    "scatter time and dequantized inside the attention kernels, so "
    "no full-precision KV tensor is ever materialized; the pool "
    "holds ~4*head_dim/(head_dim+4) times more tokens (2.7-3.6x for "
    "typical head dims). The dtype joins the engine digest/exec "
    "cache key — the warmup grid is retraced once per dtype, never "
    "in steady state. fp8 is reserved (raises until native f8 "
    "converts land). docs/serving.md 'Quantized serving'.",
)
register_env(
    "MXNET_DECODE_RING_PREFILL", int, 0,
    "decoding: minimum PADDED prompt length (length bucket) that "
    "routes prefill attention through parallel.ring_attention on a "
    "'seq' mesh — the long-context prefill path. 0 disables; the "
    "bucket length must then divide across the chosen seq axis.",
)
register_env(
    "MXNET_DECODE_MAX_TOKENS", int, 32,
    "decoding: default max_new_tokens for generate()/submit() when "
    "the request does not say (always also bounded by KV capacity: "
    "pages_per_seq_bucket_max * page_size).",
)
register_env(
    "MXNET_DECODE_QUEUE_CAP", int, 256,
    "decoding: bounded admission queue of the continuous-batching "
    "scheduler; a full queue fast-fails submit() with "
    "ServerBusyError (same backpressure contract as the one-shot "
    "serving tier).",
)
register_env(
    "MXNET_DECODE_PREFIX_CACHE", bool, True,
    "decoding: cache full prompt-prefix KV pages in a radix index "
    "and map them into new sequences via the refcount/COW fork path "
    "instead of re-prefilling (only the tail past the cached prefix "
    "is computed). Cached-but-idle pages are evicted LRU under pool "
    "pressure BEFORE any live sequence is preempted. 0 disables.",
)
register_env(
    "MXNET_DECODE_SPEC_K", int, 4,
    "decoding: draft tokens proposed per speculative step. The "
    "target verifies all K+1 positions in one fixed-shape multi-"
    "query pass and emits 1..K+1 tokens per step; output is "
    "distribution-identical to target-only decoding (exactly equal "
    "under greedy). Only active when a draft model is loaded.",
)
register_env(
    "MXNET_DECODE_SPEC_DRAFT", str, "",
    "decoding: default draft-model spec for load_decoder/"
    "DecodedModel. 'self' = the target drafts for itself (testing/"
    "CI: acceptance ~1). Empty = no draft; speculative decoding is "
    "then off unless a draft params dict is passed explicitly.",
)
register_env(
    "MXNET_DECODE_SAMPLING_TEMPERATURE", float, 0.0,
    "decoding: default sampling temperature for requests that do "
    "not pass SamplingParams. <= 0 is greedy argmax (deterministic, "
    "seed-independent — the historical decode-tier behavior).",
)
register_env(
    "MXNET_DECODE_SAMPLING_TOP_K", int, 0,
    "decoding: default top-k cutoff for sampled requests (keep the "
    "k highest-probability tokens before sampling; ties at the "
    "k-th value are kept). 0 disables the cutoff.",
)
register_env(
    "MXNET_DECODE_SAMPLING_TOP_P", float, 1.0,
    "decoding: default nucleus (top-p) mass for sampled requests — "
    "keep the smallest prefix of probability-sorted tokens whose "
    "mass reaches p (at least one token always survives). 1.0 "
    "disables the cutoff.",
)
register_env(
    "MXNET_DECODE_SAMPLING_SEED", int, 0,
    "decoding: default per-request sampling seed. All decode-tier "
    "randomness is a counter-based stream keyed by (seed, position, "
    "salt), so a request's sampled output is bit-identical across "
    "preemption/readmission and across runs.",
)
register_env(
    "MXNET_SHARD_KV_MESH", bool, True,
    "sharding: kvstore('tpu') barrier runs as a mesh jit (1-D "
    "all-device mesh, in/out_shardings, no pmap). 0 restores the "
    "legacy pmapped-psum barrier — a fallback for backends where "
    "the mesh program is unavailable.",
)
register_env(
    "MXNET_SHARD_FSDP_MIN_SIZE", int, 0,
    "sharding: parameters with fewer elements than this keep the "
    "fsdp axis OFF when resolved by advisory rules (tiny "
    "biases/norm scales cost more to reshard than they save in "
    "storage). 0 = shard everything the rules say; explicit "
    "overrides are never downgraded.",
)
register_env(
    "MXNET_SHARD_CONSTRAIN_COMPUTE", bool, True,
    "sharding: pin fsdp-stored parameters to their compute layout "
    "(fsdp axis dropped) inside the fused step trace — explicit "
    "gather-before-use; the vjp transpose of the constraint is the "
    "reduce-scatter of the gradients. 0 leaves the layout to the "
    "GSPMD propagator.",
)
register_env(
    "MXNET_PROFILING", bool, True,
    "profiling: device-side executable accounting "
    "(mxnet_tpu.profiling). Every framework-built jit compiles "
    "ahead-of-time on first call per signature, records "
    "memory_analysis/cost_analysis/compile time into the "
    "deviceStats view, and dispatches through the captured "
    "executable (one compile — no extra work). 0 restores raw jit "
    "dispatch everywhere and skips all recording "
    "(docs/observability.md).",
)
register_env(
    "MXNET_PROFILING_HBM_STRICT", bool, False,
    "profiling: escalate the HBM pre-flight warning to "
    "HBMPreflightError — a bind whose estimated footprint (params + "
    "grads + optimizer state + activations) exceeds the device "
    "memory cap fails BEFORE tracing instead of OOMing after "
    "(mxnet_tpu.profiling.preflight).",
)
register_env(
    "MXNET_PROFILING_DEVICE_MEM_BYTES", int, 0,
    "profiling: device memory cap in bytes for the HBM pre-flight. "
    "0 = ask the backend (device.memory_stats()['bytes_limit']); "
    "CPU jax reports nothing, so on CPU the pre-flight records its "
    "report without warning unless this override is set (it is how "
    "the tests fake a small device).",
)
register_env(
    "MXNET_PROFILING_OPT_FACTOR", str, "2.0",
    "profiling: optimizer-state bytes per gradient byte assumed by "
    "the HBM pre-flight (2.0 = Adam's two moments; 1.0 for "
    "momentum-SGD; 0 for plain SGD).",
)
register_env(
    "MXNET_PROFILING_TOPK", int, 20,
    "profiling: rows in the per-op device-time top-K table of the "
    "deviceTimelineStats view (/statusz, dump_profile).",
)
register_env(
    "MXNET_PROFILING_MAX_SIGS", int, 64,
    "profiling: per-wrapped-jit cap on AOT-captured input "
    "signatures; signatures beyond the cap dispatch through the raw "
    "jit uncaptured (a guard against unbounded shape churn, which "
    "would itself be the bug to fix).",
)
register_env(
    "MXNET_CALIBRATION_CACHE", str,
    "~/.cache/mxnet_tpu/calibration.json",
    "profiling: CalibrationStore persistence — measured step/forward "
    "seconds keyed by canonical graph digest + platform + kind, "
    "harvested automatically during serving/decoding warmup and fit "
    "epochs; cost_model.calibrated_cost() prefers these over the "
    "analytic estimate. Delete the file to re-calibrate "
    "(docs/observability.md).",
)
register_env(
    "MXNET_NUMERICS", bool, False,
    "numerics: enable the device-resident run-health layer "
    "(mxnet_tpu.numerics) in fit — a per-step sentinel row (loss, "
    "NaN/Inf counts, per-param-group gradient/parameter/update "
    "norms) computed inside the fused train step, drained in one "
    "device fetch per MXNET_NUMERICS_INTERVAL steps, with anomaly "
    "rules, first-bad-op attribution, and the numericsStats view "
    "(docs/observability.md 'Run health').",
)
register_env(
    "MXNET_NUMERICS_INTERVAL", int, 10,
    "numerics: steps between sentinel drains (each drain is ONE "
    "blocking device fetch). <= 0 drains only at epoch boundaries "
    "— the setting CI uses to prove fit's host-sync budget is "
    "unchanged with numerics on (ci/check_numerics.py).",
)
register_env(
    "MXNET_NUMERICS_HISTORY", int, 64,
    "numerics: sentinel rows kept in the in-memory history ring — "
    "the 'what did the norms look like before it' context attached "
    "to crash flight records on an anomaly.",
)
register_env(
    "MXNET_NUMERICS_RUNLOG", str, "",
    "numerics: path of the append-only JSONL run event log (step "
    "rows, anomalies, epoch marks; resume-friendly — a restarted "
    "run appends a 'resume' marker). '' disables; fit_auto_resume "
    "defaults it to <prefix>-runlog.jsonl when numerics is on.",
)
register_env(
    "MXNET_NUMERICS_SPIKE", str, "8.0",
    "numerics: grad-norm spike threshold — a drained global grad "
    "norm above SPIKE x its EWMA raises a grad_spike anomaly "
    "(float; EWMA warms up for a few rows first).",
)
register_env(
    "MXNET_NUMERICS_ATTRIBUTION", bool, True,
    "numerics: on a nonfinite anomaly, replay the saved step inputs "
    "through the executor's eager monitored pass to name the FIRST "
    "op whose output is non-finite (cold path; per-op host checks "
    "run only after a trip). 0 skips the replay.",
)
register_env(
    "MXNET_NUMERICS_DECODE_GUARD", bool, False,
    "numerics: decode-tier logits guard — each decode step also "
    "emits a device-side count of non-finite logits on active rows, "
    "drained every MXNET_NUMERICS_INTERVAL steps into "
    "decodingStats (nonfinite_logit_steps / nonfinite_logits).",
)
register_env(
    "MXNET_EXEC_CACHE_DIR", str, "",
    "disk tier of the exec cache (mxnet_tpu.exec_cache_disk): a "
    "directory holding per-entry records (optimized canonical graph "
    "JSON, input signatures, sharding digest) plus the AOT-serialized "
    "executables of every captured program, with jax's persistent "
    "compilation cache underneath at <dir>/xla (unless "
    "JAX_COMPILATION_CACHE_DIR places it elsewhere). A process "
    "restart then rebinds with ZERO jax traces and ZERO XLA compiles "
    "(cache_stats()['disk_hits'] counts the wins). Empty = in-memory "
    "cache only, the pre-disk behavior (docs/perf.md 'Cold starts').",
)
register_env(
    "MXNET_EXEC_CACHE_DISK_BYTES", int, 1 << 30,
    "size cap in bytes on the MXNET_EXEC_CACHE_DIR entry store: after "
    "every write the least-recently-used entries (record + serialized "
    "executables; hit time = file mtime) are evicted until the store "
    "fits. The jax compilation cache under <dir>/xla is not counted — "
    "jax bounds it itself. 0 disables eviction.",
)
register_env(
    "MXNET_BUNDLE_STRICT", bool, False,
    "serving bundles: escalate restore degradations to errors. By "
    "default a bundle whose executables were serialized by a "
    "different jaxlib/platform loads with a warning and falls back to "
    "re-tracing (correct, just not zero-compile); strict mode raises "
    "BundleError instead — deploys that REQUIRE the zero-compile "
    "contract fail loudly rather than silently paying warmup "
    "(docs/serving.md 'Bundles').",
)
register_env(
    "MXNET_BUNDLE_VERIFY", bool, True,
    "serving bundles: verify the manifest's parameter content hash "
    "(over array names, dtypes, shapes, bytes) on load_bundle; a "
    "mismatch raises BundleError (tamper/corruption rejection). 0 "
    "skips hashing — only for bundles on trusted read-only media "
    "where load latency matters more.",
)
register_env(
    "MXNET_BUNDLE_QUANTIZE", str, "",
    "serving bundles: default save_bundle quantization scheme. "
    "'int8' stores the parameter set weight-only int8 with "
    "per-channel (last-axis) float32 scales — ~4x smaller artifact; "
    "restore dequantizes on load so saved AOT executables still "
    "replay at zero traces/compiles. Empty (default) stores full "
    "precision. The explicit save_bundle(quantize=...) argument "
    "wins over this env.",
)
register_env(
    "MXNET_BUNDLE_QUANTIZE_OVERRIDE", bool, False,
    "serving bundles: load a bundle whose manifest quantization "
    "record and stored arrays DISAGREE about precision (stripped "
    "scale planes or stripped record). Default refuses with "
    "BundleError — a silent precision mismatch changes what the "
    "model computes; 1 downgrades the refusal to a warning.",
)
register_env(
    "MXNET_FLEET_REPLICAS", int, 2,
    "fleet: number of replica worker processes the router spawns at "
    "start (mxnet_tpu.fleet.FleetRouter / tools/mx_fleet.py). Each "
    "replica restores the SAME serving bundle via load_bundle, so "
    "spin-up is zero-trace/zero-compile; the autoscaler may grow or "
    "shrink the set afterwards within [min_replicas, max_replicas] "
    "(docs/fleet.md).",
)
register_env(
    "MXNET_FLEET_PORT", int, 0,
    "fleet: TCP port the router's control-plane listener binds on "
    "127.0.0.1 (replicas dial back to it, the CLI's status/scale/"
    "drain commands use it too). 0 = pick an ephemeral port and "
    "report it in status() / the start banner — the default for "
    "tests and single-host serving.",
)
register_env(
    "MXNET_FLEET_HEARTBEAT_MS", int, 200,
    "fleet: replica heartbeat period in ms. Every beat carries queue "
    "depth, the servingStats/decodingStats snapshot, and the radix-"
    "cache digest (full cached_prefixes advertisement only when the "
    "digest changed) — the inputs of prefix-affinity routing and "
    "autoscaling. A replica silent for 5 heartbeat periods is marked "
    "dead and its in-flight requests are re-admitted elsewhere.",
)
register_env(
    "MXNET_FLEET_QUEUE_HIGH", int, 8,
    "fleet autoscaler: grow threshold — when the mean per-replica "
    "queue depth stays at or above this for `patience` consecutive "
    "observations, one replica is added (up to max_replicas). Set "
    "well above MXNET_FLEET_QUEUE_LOW; the gap is the hysteresis "
    "band that stops scale flapping.",
)
register_env(
    "MXNET_FLEET_QUEUE_LOW", int, 1,
    "fleet autoscaler: shrink threshold — when the mean per-replica "
    "queue depth stays at or below this for `patience` consecutive "
    "observations, one replica is drained and removed (down to "
    "min_replicas). Shrink always goes through drain: the victim "
    "stops admitting, finishes or hands off live decodes, then "
    "exits — zero request loss.",
)
register_env(
    "MXNET_FLEET_DRAIN_TIMEOUT_MS", int, 5000,
    "fleet: how long a draining replica may run live decodes to "
    "completion before the rest are handed off (each unfinished "
    "request's resume state — tokens so far + sampling seed/position "
    "— returns to the router for re-admission elsewhere, bit-"
    "identical under counter-based sampling). Also the router's "
    "escalation deadline: a replica that missed it is killed and "
    "its requests re-admitted from the router's own token record.",
)
register_env(
    "MXNET_ELASTIC_PORT", int, 0,
    "elastic training: TCP port the ElasticCoordinator's membership "
    "listener binds on 127.0.0.1 (worker agents dial it with a hello "
    "frame; `fit_elastic` reads it when no --connect endpoint is "
    "given). 0 = pick an ephemeral port and report it in status() — "
    "the default for tests and single-host runs (docs/elastic.md).",
)
register_env(
    "MXNET_ELASTIC_HEARTBEAT_MS", int, 200,
    "elastic training: worker heartbeat period in ms. Every beat "
    "carries the worker's last completed global step, its exec-cache "
    "trace count (the zero-retrace evidence after a re-grow) and its "
    "post-step param digest (cross-worker bitwise divergence shows "
    "up as a counted mismatch, not silent drift). A worker silent "
    "for 5 periods is declared dead and a shrink transition starts.",
)
register_env(
    "MXNET_ELASTIC_QUIESCE_TIMEOUT_MS", int, 5000,
    "elastic training: how long the coordinator waits at the quiesce "
    "barrier for every surviving worker to acknowledge the step "
    "boundary before declaring stragglers dead and resharding "
    "without them. The quiesce wall (time actually spent here) is "
    "reported per transition in elasticStats.",
)
register_env(
    "MXNET_ELASTIC_LOGICAL_SHARDS", int, 0,
    "elastic training: number of LOGICAL data/gradient shards the "
    "job is cut into — fixed for the job lifetime so the training "
    "arithmetic (which examples form global step N, the order their "
    "micro-batch gradients combine in) is invariant to membership "
    "and final params stay bit-identical across shrink/re-grow. "
    "Physical workers own logical shards round-robin (shard s -> "
    "rank s % world). 0 = use the world size at job start.",
)
register_env(
    "MXNET_ELASTIC_MIN_WORLD", int, 1,
    "elastic training: smallest membership the job may shrink to. A "
    "death that would take the world below this parks the job at the "
    "quiesce barrier (state persisted via the numerics run log) "
    "until a joiner arrives instead of continuing under-provisioned.",
)
register_env(
    "MXNET_ELASTIC_REJOIN_MS", int, 10000,
    "elastic training: worker auto-rejoin budget. When a worker "
    "loses its coordinator connection (coordinator restart, network "
    "blip) `fit_elastic` keeps re-dialing the endpoint with fresh "
    "hello frames for this many ms before giving up; a successful "
    "re-dial joins as a fresh member and is bootstrapped through the "
    "normal re-grow transition — no manual restart choreography.",
)
register_env(
    "MXNET_LOCK_WITNESS", str, "",
    "analysis: runtime lock witness "
    "(mxnet_tpu.analysis.lockwitness). '' / 'off' = disabled (the "
    "threading lock factories are untouched); '1' / 'record' = "
    "record every thread's acquisition order into a dynamic "
    "held-before graph, collecting lock-order cycles in "
    "violations(); 'raise' = additionally raise LockOrderViolation "
    "at the acquisition attempt that completes a cycle — the "
    "would-be deadlock becomes a diagnosed exception instead of a "
    "hang. On in the threaded test modules and the CI race-gate "
    "soak (docs/analysis.md).",
)
