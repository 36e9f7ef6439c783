"""mxlint rule set: the framework-specific invariants, checked at the AST.

PRs 1-4 made this stack TPU-fast by construction — zero steady-state
retraces (exec_cache), zero per-step host<->device sync (pipelined fit),
registered MXNET_* knobs, deterministic worker streams — but those
invariants were enforced only dynamically, by one runtime gate script
per code path (ci/check_no_perstep_jit.py, ci/check_no_perstep_sync.py).
A regression in any OTHER path shipped silently. These rules are the
static half (the Relay/Glow lesson from PAPERS.md: verify at the graph/
source level and fail fast with good diagnostics, not deep inside the
backend):

  MX001  host-sync call on a declared hot path
  MX002  retrace hazard: jax.jit of a per-call / per-iteration closure
  MX003  unregistered MXNET_* environment read
  MX004  concurrency hygiene (bare except, implicit-daemon threads,
         raw Lock.acquire)
  MX005  nondeterminism: global-RNG draws outside mxnet_tpu.random,
         wall-clock in cache keys
  MX009  raw pl.pallas_call outside the kernel entry points, or an
         allowlisted kernel module missing its lax fallback twin

Every rule is a pure function over one parsed file (`FileContext`);
the engine (lint.py) owns walking, suppression, baseline, and output.
This module is stdlib-only so `tools/mxlint.py` never imports jax.
"""
from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field

# --------------------------------------------------------------------------
# Hot-path manifest (MX001). Paths are repo-relative with "/" separators;
# values are qualified function names ("Class.method" or "function"), or
# "*" for every function in the file. These are the per-step code paths
# whose zero-sync property the runtime gates prove on ONE path each —
# the manifest extends the guarantee to every listed function statically.
# --------------------------------------------------------------------------
HOT_PATH_MANIFEST = {
    # pipelined fit internals (PR 3): one dispatch per step, fetches
    # only at log intervals / epoch boundaries
    "mxnet_tpu/module/base_module.py": (
        "BaseModule.fit", "BaseModule.forward_backward",
        "_DispatchWindow.admit", "_DispatchWindow.drain",
    ),
    "mxnet_tpu/module/module.py": (
        "Module.forward", "Module.backward", "Module.update",
    ),
    # dynamic batcher flush loop (PR 2): assembly/flush must never
    # block on device values
    "mxnet_tpu/serving/batcher.py": "*",
    "mxnet_tpu/serving/server.py": ("ModelServer._worker_loop",),
    # device-prefetch worker (PR 4): staging is async device_put only
    "mxnet_tpu/data/device_prefetch.py": (
        "DevicePrefetchIter._stage_loop", "DevicePrefetchIter._to_device",
        "DevicePrefetchIter.next", "DevicePrefetchIter._next_sync",
    ),
    # fused train step (PR 1): the whole step is one donated XLA launch
    "mxnet_tpu/parallel/dp_step.py": (
        "FusedTrainStep.step", "FusedTrainStep.run_steps",
        "FusedTrainStep._place_data", "FusedTrainStep._absorb",
    ),
    # monitor (numerics PR): tic fences once, toc drains once — no
    # per-tensor fetches on the fit loop
    "mxnet_tpu/monitor.py": (
        "Monitor.tic", "Monitor.toc", "Monitor.toc_print",
        "Monitor._on_tensor", "Monitor._render_batch",
    ),
    # numerics run-health hot hooks (numerics PR): note_batch keeps a
    # reference; after_batch only counts steps between drains
    "mxnet_tpu/numerics/__init__.py": (
        "NumericsMonitor.note_batch", "NumericsMonitor.after_batch",
    ),
    # device-resident metric accumulation (PR 3)
    "mxnet_tpu/metric.py": ("EvalMetric.update_device",),
    # graph-pass pipeline entry points (PR 6): they run inside every
    # bind, ahead of the exec-cache lookup — a host sync here would
    # serialize binding (constant folding's host transfer lives in
    # transforms.fold, which runs at most once per canonical graph)
    "mxnet_tpu/passes/manager.py": (
        "optimize_for_bind", "PassManager.run", "pipeline_spec",
    ),
    # telemetry hot paths (PR 7): span recording runs inside every
    # serving request and every fit step; instrument updates and the
    # exporter handler read live counters — none may touch the device
    "mxnet_tpu/telemetry/trace.py": "*",
    "mxnet_tpu/telemetry/registry.py": (
        "Counter.inc", "Gauge.set", "Histogram.observe",
    ),
    "mxnet_tpu/telemetry/http.py": (
        "TelemetryHandler.do_GET", "statusz",
    ),
    # continuous-decode step loop + allocator (PR 8): the scheduler
    # runs admission/growth/step every token for every live sequence;
    # the only sanctioned syncs are the engine's np.asarray token
    # fetches (one per prefill, one per step — EOS/stream need them)
    "mxnet_tpu/decoding/blocks.py": "*",
    # the radix lookup runs inside every admission, the sampler and
    # the speculative propose/verify forwards run inside the jitted
    # step programs — none may fetch or retrace
    "mxnet_tpu/decoding/prefix.py": "*",
    "mxnet_tpu/decoding/sampling.py": "*",
    "mxnet_tpu/decoding/speculative.py": "*",
    "mxnet_tpu/decoding/engine.py": (
        "DecodeEngine.prefill", "DecodeEngine.launch_prefill",
        "DecodeEngine.fetch_prefill", "DecodeEngine._launch_chunks",
        "DecodeEngine.step", "DecodeEngine.launch_step",
        "DecodeEngine.fetch_step", "DecodeEngine.next_tokens",
        "DecodeEngine.spec_step",
        "DecodeEngine.copy_page", "DecodeEngine.pool_stats",
        "DecodeEngine.prefill_pages", "DecodeEngine.table_shape",
    ),
    # the second block's forwards run inside the jitted chunk-prefill
    # and decode programs: pure jax on traced values
    "mxnet_tpu/decoding/sparse_latent.py": "*",
    # what the blocks share, and the third block: the same
    "mxnet_tpu/decoding/layers.py": "*",
    "mxnet_tpu/decoding/window_mixed.py": "*",
    "mxnet_tpu/decoding/scheduler.py": (
        "ContinuousScheduler._admit", "ContinuousScheduler._grow",
        "ContinuousScheduler._step", "ContinuousScheduler._preempt",
        "ContinuousScheduler._reclaim_one",
        "ContinuousScheduler._free_one_page",
        "ContinuousScheduler._check_deadlines",
        "ContinuousScheduler._check_cancelled",
        "ContinuousScheduler._handle_token",
        "ContinuousScheduler._resolve",
        "ContinuousScheduler._turn_ahead",
        "ContinuousScheduler._launch_ahead",
        "ContinuousScheduler._retire", "ContinuousScheduler._settle",
        "ContinuousScheduler._pending",
        "ContinuousScheduler._release_windows",
        "ContinuousScheduler._grow_side",
        "ContinuousScheduler._drop_pages",
        "ContinuousScheduler._step_attrs",
        "ContinuousScheduler._note_step",
    ),
    "mxnet_tpu/decoding/stats.py": (
        "DecodeStats.note_step", "DecodeStats.note_prefill",
        "DecodeStats.note_preempted", "DecodeStats.note_pool",
        "DecodeStats.note_spec", "DecodeStats.note_prefix_reuse",
        "DecodeStats.note_quant_clips", "DecodeStats.note_counters",
        "DecodeStats.note_released",
    ),
    # KV quantization (quant PR): quantize-at-scatter / dequantize-at-
    # gather run INSIDE the jitted prefill/decode/attention programs —
    # pure jax ops on traced values, never a fetch or a retrace
    "mxnet_tpu/decoding/quant.py": "*",
    # sharding plan resolution + jit lowering (PR 11): resolve/digest
    # run inside every bind (ahead of the exec-cache lookup) and the
    # lower helpers run inside the fused-step trace — metadata only,
    # never a device fetch
    "mxnet_tpu/sharding/plan.py": (
        "ShardingPlan.resolve", "ShardingPlan.named_shardings",
        "ShardingPlan.digest", "ShardingPlan.compute_spec",
    ),
    "mxnet_tpu/sharding/lower.py": "*",
    # executable accounting (PR 12): the instrumented-jit wrapper sits
    # on EVERY dispatch of every profiled program, and the stats
    # snapshots serve /metrics scrapes — bookkeeping only, never a
    # device fetch (the one sanctioned device read, memory_analysis,
    # happens at compile time inside _capture, off the hot path)
    "mxnet_tpu/profiling/device_stats.py": (
        "InstrumentedJit.__call__", "device_stats", "records_for",
    ),
    "mxnet_tpu/profiling/timeline.py": (
        "timeline_stats", "aggregate_device_events",
    ),
    # fleet control plane (PR 17): routing and frame relay sit on
    # every fleet request and every streamed token; the wire send is
    # an outbox enqueue and the affinity lookup is pure digest math —
    # none may fetch, sleep, or wait
    "mxnet_tpu/fleet/router.py": (
        "FleetRouter.submit", "FleetRouter._pick_replica",
        "FleetRouter._load", "FleetRouter._on_message",
    ),
    "mxnet_tpu/fleet/replica.py": (
        "ReplicaWorker._handle_decode", "ReplicaWorker._heartbeat",
    ),
    "mxnet_tpu/fleet/affinity.py": "*",
    "mxnet_tpu/fleet/wire.py": ("Channel.send", "send_frame"),
    # elastic control plane (PR 19): the per-step frame handlers run
    # once per global step per worker and the heartbeat/codec paths run
    # continuously — pure numpy + outbox enqueues, never a device
    # fetch, a sleep, or a socket op under the coordinator lock
    "mxnet_tpu/elastic/coordinator.py": (
        "ElasticCoordinator._on_grads",
        "ElasticCoordinator._on_slices",
        "ElasticCoordinator._on_heartbeat",
        "ElasticCoordinator._dispatch",
    ),
    "mxnet_tpu/elastic/agent.py": (
        "ElasticWorker._one_step", "ElasticWorker._hb_loop",
        "ElasticWorker._await", "ElasticWorker._log_consumed",
    ),
    "mxnet_tpu/elastic/codec.py": "*",
}

# Methods that force a host<->device round-trip (MX001).
_SYNC_METHODS = {"asnumpy", "wait_to_read"}

# Global-RNG sampling entry points (MX005). Constructing an explicit
# generator (RandomState/Generator/Philox/default_rng) is NOT flagged —
# an owned, seedable stream is exactly what the rule asks for.
_PY_RANDOM_FNS = {
    "random", "randint", "uniform", "choice", "choices", "shuffle",
    "sample", "gauss", "normalvariate", "randrange", "betavariate",
    "expovariate", "triangular", "getrandbits", "seed",
}
_NP_RANDOM_FNS = {
    "random", "rand", "randn", "randint", "random_sample", "ranf",
    "uniform", "normal", "standard_normal", "choice", "shuffle",
    "permutation", "beta", "binomial", "poisson", "exponential",
    "gamma", "laplace", "multinomial", "seed",
}
_WALLCLOCK_CALLS = {
    "time.time", "time.time_ns", "datetime.datetime.now",
    "datetime.datetime.utcnow", "datetime.date.today",
}

# MX005 applies to library code only: the determinism contract is that
# mxnet_tpu/ draws route through mxnet_tpu.random (so mx.random.seed
# controls them); examples/ and tools/ are user-side code.
_LIBRARY_PREFIX = "mxnet_tpu/"
_MX005_EXEMPT = {
    # the routing target itself: owns the seeded generators
    "mxnet_tpu/random.py",
}


@dataclass
class RawFinding:
    rule: str
    line: int
    col: int
    message: str


@dataclass
class FileContext:
    """One parsed file plus the cross-file facts rules need."""

    relpath: str            # repo-relative, "/"-separated
    tree: ast.AST
    lines: list[str]
    registered_envs: set = field(default_factory=set)

    def is_library(self):
        return self.relpath.startswith(_LIBRARY_PREFIX)


# --------------------------------------------------------------------------
# shared AST helpers
# --------------------------------------------------------------------------
def _import_map(tree):
    """Local name -> dotted module path for plain imports."""
    out = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                out[a.asname or a.name] = f"{node.module}.{a.name}"
    return out


def _dotted(node, imports):
    """Resolve an expression to a dotted name through the import map:
    `jnp.array` -> "jax.numpy.array" when `import jax.numpy as jnp`.
    Returns None for anything that is not a plain Name/Attribute chain."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    root = imports.get(node.id, node.id)
    parts.append(root)
    return ".".join(reversed(parts))


def _qualnames(tree):
    """(node, qualified name) for every def: "Class.method" / "fn" /
    "fn.nested"."""
    out = []

    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qn = f"{prefix}{child.name}"
                out.append((child, qn))
                # nested defs belong to their enclosing hot function
                walk(child, f"{qn}.")
            elif isinstance(child, ast.ClassDef):
                walk(child, f"{prefix}{child.name}.")
            else:
                walk(child, prefix)

    walk(tree, "")
    return out


def _str_const(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


# --------------------------------------------------------------------------
# MX001 — host-sync calls on declared hot paths
# --------------------------------------------------------------------------
def check_mx001(ctx):
    manifest = HOT_PATH_MANIFEST.get(ctx.relpath)
    if manifest is None:
        return []
    qual = _qualnames(ctx.tree)
    imports = _import_map(ctx.tree)
    findings = []

    def covers(qn):
        if manifest == "*":
            return True
        # nested defs inherit the hot-path property of their parent
        return any(qn == m or qn.startswith(m + ".") for m in manifest)

    seen = set()
    for fn_node, qn in qual:
        if not covers(qn):
            continue
        for node in ast.walk(fn_node):
            if (node.__class__, id(node)) in seen:
                continue  # nested hot def already walked by its parent
            seen.add((node.__class__, id(node)))
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in _SYNC_METHODS:
                findings.append(RawFinding(
                    "MX001", node.lineno, node.col_offset,
                    f"`.{f.attr}()` in hot-path function `{qn}`: blocks "
                    "the dispatch pipeline on a device round-trip; keep "
                    "values device-resident (see docs/perf.md) or fetch "
                    "at log/epoch boundaries only"))
            elif (isinstance(f, ast.Attribute) and f.attr == "item"
                    and not node.args and not node.keywords):
                findings.append(RawFinding(
                    "MX001", node.lineno, node.col_offset,
                    f"`.item()` in hot-path function `{qn}`: a scalar "
                    "fetch is still a full device sync; accumulate on "
                    "device and drain at get() time"))
            else:
                dn = _dotted(f, imports)
                if dn == "numpy.array":
                    findings.append(RawFinding(
                        "MX001", node.lineno, node.col_offset,
                        f"`np.array(...)` in hot-path function `{qn}`: "
                        "materializes (and for device arrays, fetches) "
                        "its argument on host; use jnp ops to stay on "
                        "device, or np.asarray for known-host data"))
    return findings


# --------------------------------------------------------------------------
# MX002 — retrace hazards
# --------------------------------------------------------------------------
def check_mx002(ctx):
    imports = _import_map(ctx.tree)
    findings = []

    def is_jit(node):
        return _dotted(node, imports) in ("jax.jit", "jax.pmap")

    def walk(node, loop_depth):
        for child in ast.iter_child_nodes(node):
            d = loop_depth
            if isinstance(child, (ast.For, ast.While, ast.AsyncFor)):
                d += 1
            if isinstance(child, ast.Call):
                if is_jit(child.func) and d > 0:
                    findings.append(RawFinding(
                        "MX002", child.lineno, child.col_offset,
                        "jax.jit inside a loop: every iteration builds "
                        "a fresh closure, so every call is a fresh "
                        "trace+compile; hoist the jit (or go through "
                        "exec_cache, which keys compiled programs by "
                        "graph signature)"))
                elif (isinstance(child.func, ast.Call)
                        and is_jit(child.func.func)):
                    findings.append(RawFinding(
                        "MX002", child.lineno, child.col_offset,
                        "jax.jit(...)(...) immediately invoked: the "
                        "jitted closure is rebuilt per call, which "
                        "guarantees a retrace every time; bind the jit "
                        "once and reuse it"))
            walk(child, d)

    walk(ctx.tree, 0)
    return findings


# --------------------------------------------------------------------------
# MX003 — unregistered MXNET_* environment reads
# --------------------------------------------------------------------------
def check_mx003(ctx):
    imports = _import_map(ctx.tree)
    findings = []

    def flag(node, name, how):
        findings.append(RawFinding(
            "MX003", node.lineno, node.col_offset,
            f"{how} reads {name!r}, which is not declared in the env "
            "registry (mxnet_tpu/utils register_env): undocumented knobs "
            "drift — register it so docs/env_vars.md includes it"))

    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            dn = _dotted(node.func, imports)
            if dn is not None and (
                    dn.endswith("os.environ.get") or dn == "os.getenv"
                    or dn.endswith(".environ.get")):
                name = _str_const(node.args[0]) if node.args else None
                if (name and name.startswith("MXNET_")
                        and name not in ctx.registered_envs):
                    flag(node, name, f"`{dn}`")
        elif isinstance(node, ast.Subscript) and isinstance(
                node.ctx, ast.Load):
            dn = _dotted(node.value, imports)
            if dn is not None and dn.endswith("os.environ"):
                name = _str_const(node.slice)
                if (name and name.startswith("MXNET_")
                        and name not in ctx.registered_envs):
                    flag(node, name, "`os.environ[...]`")
    return findings


# --------------------------------------------------------------------------
# MX004 — concurrency hygiene
# --------------------------------------------------------------------------
_COND_CTORS = {"threading.Condition", "multiprocessing.Condition"}
_EVENT_CTORS = {"threading.Event", "multiprocessing.Event"}


def _sync_prims(tree, imports):
    """({self-attr}, {local name}) pairs for Condition and Event
    objects constructed in this file."""
    cond_self, cond_local, event_self, event_local = (
        set(), set(), set(), set())
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)):
            continue
        dn = _dotted(node.value.func, imports)
        if dn not in _COND_CTORS and dn not in _EVENT_CTORS:
            continue
        is_cond = dn in _COND_CTORS
        for tgt in node.targets:
            if (isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"):
                (cond_self if is_cond else event_self).add(tgt.attr)
            elif isinstance(tgt, ast.Name):
                (cond_local if is_cond else event_local).add(tgt.id)
    return cond_self, cond_local, event_self, event_local


def check_mx004(ctx):
    imports = _import_map(ctx.tree)
    findings = []
    cond_self, cond_local, event_self, event_local = _sync_prims(
        ctx.tree, imports)

    def prim_kind(recv):
        if (isinstance(recv, ast.Attribute)
                and isinstance(recv.value, ast.Name)
                and recv.value.id == "self"):
            if recv.attr in cond_self:
                return "cond"
            if recv.attr in event_self:
                return "event"
        elif isinstance(recv, ast.Name):
            if recv.id in cond_local:
                return "cond"
            if recv.id in event_local:
                return "event"
        return None

    # every Call node lexically inside a While body — the sanctioned
    # home for Condition.wait (re-test the predicate after waking)
    in_while = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.While):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    in_while.add(id(sub))

    # hot-path coverage for the Event.wait check
    manifest = HOT_PATH_MANIFEST.get(ctx.relpath)
    hot_calls = set()
    if manifest is not None:
        for fn_node, qn in _qualnames(ctx.tree):
            if manifest == "*" or any(
                    qn == m or qn.startswith(m + ".")
                    for m in manifest):
                for sub in ast.walk(fn_node):
                    if isinstance(sub, ast.Call):
                        hot_calls.add(id(sub))

    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            findings.append(RawFinding(
                "MX004", node.lineno, node.col_offset,
                "bare `except:` also swallows KeyboardInterrupt/"
                "SystemExit — a worker loop that catches these can "
                "never be shut down; catch `Exception` (or narrower)"))
        elif isinstance(node, ast.Call):
            dn = _dotted(node.func, imports)
            if dn == "threading.Thread":
                if not any(k.arg == "daemon" for k in node.keywords):
                    findings.append(RawFinding(
                        "MX004", node.lineno, node.col_offset,
                        "threading.Thread without an explicit daemon=: "
                        "an implicit non-daemon thread with no join "
                        "path hangs interpreter exit; pass daemon=True, "
                        "or daemon=False alongside a join"))
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "acquire"
                    and _dotted(node.func, imports) != "locale.acquire"):
                findings.append(RawFinding(
                    "MX004", node.lineno, node.col_offset,
                    "raw `.acquire()`: an exception before the matching "
                    "release() leaves the lock held forever; use "
                    "`with lock:`"))
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "wait"):
                kind = prim_kind(node.func.value)
                timed = (node.args or any(
                    k.arg == "timeout" for k in node.keywords))
                if kind == "cond" and id(node) not in in_while:
                    findings.append(RawFinding(
                        "MX004", node.lineno, node.col_offset,
                        "`Condition.wait()` outside a `while`-predicate "
                        "loop: wakeups can be spurious and notify_all "
                        "races the predicate — always re-test in a loop "
                        "(`while not pred: cond.wait(...)`)"))
                elif (kind == "event" and not timed
                        and id(node) in hot_calls):
                    findings.append(RawFinding(
                        "MX004", node.lineno, node.col_offset,
                        "untimed `Event.wait()` in a hot-path-manifest "
                        "function: if the setter dies this thread parks "
                        "forever with no diagnostic; use a timeout and "
                        "re-check liveness"))
    return findings


# --------------------------------------------------------------------------
# MX005 — nondeterminism
# --------------------------------------------------------------------------
def check_mx005(ctx):
    if not ctx.is_library() or ctx.relpath in _MX005_EXEMPT:
        return []
    imports = _import_map(ctx.tree)
    findings = []

    # function spans for the wall-clock-in-key check
    key_spans = []
    for node, qn in _qualnames(ctx.tree):
        leaf = qn.rsplit(".", 1)[-1].lower()
        if "key" in leaf or "signature" in leaf:
            key_spans.append(
                (node.lineno, getattr(node, "end_lineno", node.lineno),
                 qn))

    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        dn = _dotted(node.func, imports)
        if dn is None:
            continue
        if dn.startswith("random.") and dn.split(".", 1)[1] in \
                _PY_RANDOM_FNS:
            findings.append(RawFinding(
                "MX005", node.lineno, node.col_offset,
                f"`{dn}` draws from the process-global stdlib RNG, which "
                "mx.random.seed does NOT control: two hosts (or two "
                "runs) diverge silently; route through "
                "mxnet_tpu.random.py_rng()"))
        elif dn.startswith("numpy.random.") and \
                dn.split(".")[-1] in _NP_RANDOM_FNS:
            findings.append(RawFinding(
                "MX005", node.lineno, node.col_offset,
                f"`{dn}` uses numpy's global RNG directly; library code "
                "must route through mxnet_tpu.random.np_rng() so the "
                "draw is visibly under mx.random.seed control"))
        elif dn in _WALLCLOCK_CALLS:
            for lo, hi, qn in key_spans:
                if lo <= node.lineno <= hi:
                    findings.append(RawFinding(
                        "MX005", node.lineno, node.col_offset,
                        f"wall-clock `{dn}` inside `{qn}`: a time-derived "
                        "cache key/signature is different on every "
                        "process, defeating the cache and any cross-host "
                        "agreement; key on content, not time"))
                    break
    return findings


# --------------------------------------------------------------------------
# MX009 — pallas_call outside the sanctioned kernel entry points
# --------------------------------------------------------------------------
# A Pallas kernel lives in one of the two attention modules, each of
# which carries its own reference implementation: a raw pl.pallas_call
# anywhere else is a hand-rolled kernel with nothing to be compared
# with and nothing to fall back to. Even in the allowlisted modules
# the rule demands visible fallback evidence (a module-level def whose
# name says "lax"/"reference", or a kernel-registry dict with a "lax"
# key), so the escape hatch never silently loses its escape.
_MX009_ALLOWED = {
    "mxnet_tpu/decoding/attention.py",
    "mxnet_tpu/parallel/attention.py",
}


def _mx009_has_fallback(tree):
    """Module-level evidence of a lax twin: a top-level (or class-level)
    function whose name advertises the reference path, or a registry
    dict literal that maps the "lax" choice."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            name = node.name.lower()
            if "lax" in name or "reference" in name:
                return True
        elif isinstance(node, ast.Dict):
            for key in node.keys:
                if _str_const(key) == "lax":
                    return True
    return False


def check_mx009(ctx):
    imports = _import_map(ctx.tree)
    calls = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        dn = _dotted(node.func, imports)
        if dn is not None and (dn == "pallas_call"
                               or dn.endswith(".pallas_call")):
            calls.append(node)
    if not calls:
        return []
    findings = []
    if ctx.relpath not in _MX009_ALLOWED:
        for node in calls:
            findings.append(RawFinding(
                "MX009", node.lineno, node.col_offset,
                "raw `pl.pallas_call` outside the kernel entry points "
                "(decoding/attention.py, parallel/attention.py): a "
                "hand-rolled kernel with no lax twin has nothing to be "
                "compared with or to fall back to — add the file to "
                "the allowlist WITH a lax twin"))
    elif not _mx009_has_fallback(ctx.tree):
        for node in calls:
            findings.append(RawFinding(
                "MX009", node.lineno, node.col_offset,
                "`pl.pallas_call` in an allowlisted kernel module with "
                "no registered lax fallback: keep a module-level "
                "reference implementation (a `*_lax`/`*_reference` def "
                "or a kernel dict with a \"lax\" entry) so non-TPU "
                "platforms and parity checks always have a twin"))
    return findings


#: rule code -> (checker, one-line summary) — the engine iterates this.
ALL_RULES = {
    "MX001": (check_mx001, "host-sync call on a declared hot path"),
    "MX002": (check_mx002, "jax.jit of a per-call/per-iteration closure"),
    "MX003": (check_mx003, "unregistered MXNET_* environment read"),
    "MX004": (check_mx004, "concurrency hygiene"),
    "MX005": (check_mx005, "nondeterministic draw / wall-clock key"),
    "MX009": (check_mx009, "pallas_call outside kernel entry points"),
}

#: project-scope rules — computed once over the whole tree by
#: analysis.concurrency (MX006-MX008), analysis.effects (MX010-MX012),
#: and analysis.protocol (MX013); they need the interprocedural call
#: graph or cross-file frame matching, not one file, but are
#: registered here so --select/--list-rules see a single rule
#: namespace. The engine routes their findings through the same
#: per-file suppressions and baseline as MX001-MX005.
PROJECT_RULES = {
    "MX006": "blocking call while holding a lock",
    "MX007": "lock-order inversion (held-before cycle)",
    "MX008": "attribute written both inside and outside its lock",
    "MX010": "side effect in a function reachable from a jit entry",
    "MX011": "name read after being donated to a jitted call",
    "MX012": "unordered iteration / unsorted json on a digest path",
    "MX013": "wire-protocol drift (sender vs handler mismatch)",
}


def collect_registered_envs(paths):
    """Every string literal passed as the first argument to a
    register_env(...) call anywhere in `paths` (files or dirs). The
    registry in mxnet_tpu/utils/__init__.py is the canonical source;
    scanning all files lets subsystems register their own knobs."""
    names = set()
    for path in _iter_py(paths):
        try:
            with open(path, encoding="utf-8") as f:
                src = f.read()
        except OSError:
            continue
        if "register_env" not in src:
            continue
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                f_ = node.func
                fname = f_.attr if isinstance(f_, ast.Attribute) else \
                    getattr(f_, "id", None)
                if fname == "register_env" and node.args:
                    s = _str_const(node.args[0])
                    if s:
                        names.add(s)
    return names


def _iter_py(paths):
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(".py"):
                yield p
        else:
            for dirpath, dirnames, files in os.walk(p):
                dirnames[:] = [d for d in dirnames
                               if d != "__pycache__"]
                for fn in sorted(files):
                    if fn.endswith(".py"):
                        yield os.path.join(dirpath, fn)
