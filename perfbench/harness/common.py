"""What every cell shares: arguments, the look for a chip, the compile
cache, the compile counter, the peak table, quantiles, the per-layer
readers and the result line."""
import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

T_PROCESS_START = time.perf_counter()
NO_CHIP_EXIT = 3


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_py(path, name):
    """Import one file of the benchmark by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(device_kind, root):
    """The chip's published peaks; a device without a row is an error."""
    table = load_json(os.path.join(root, "perfbench/harness/peaks.json"))
    row = table["devices"].get(device_kind)
    if row is None:
        raise RuntimeError(
            f"no row for device_kind {device_kind!r} in "
            "perfbench/harness/peaks.json: add one with its source")
    return row


def quantile(values, q):
    """Nearest-rank percentile of a non-empty list."""
    vs = sorted(values)
    idx = min(len(vs) - 1, max(0, int(round(q * (len(vs) - 1)))))
    return vs[idx]


class CompileCounter:
    """Backend compiles and persistent-cache hits, from jax.monitoring:
    either one inside the window means a program was first built there."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += seconds

    def built(self):
        """Programs built so far (a cache hit is reported as a compile
        of a few ms too, so compiles alone counts each once)."""
        return self.compiles


class GcWatch:
    """Garbage-collection pauses inside the window (gc.callbacks),
    printed with the run's counts: a host stall of some hundred
    milliseconds is device idle time in a loop that feeds the device one
    step ahead, and this tells a collection apart from the other causes
    (on the chip: under 0.2 ms each, so not the cause; PERF.md).
    `open()` collects what set-up left behind first."""

    def __init__(self):
        self.pauses = []
        self._t = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((time.perf_counter() - self._t,
                                info.get("generation")))
            self._t = None

    def open(self):
        gc.collect()
        self.pauses = []
        gc.callbacks.append(self._cb)

    def close(self):
        if self._cb in gc.callbacks:
            gc.callbacks.remove(self._cb)
        top = sorted(self.pauses, reverse=True)[:3]
        return {"gc_collections": len(self.pauses),
                "gc_pause_ms_total": round(
                    sum(p for p, _ in self.pauses) * 1e3, 3),
                "gc_pause_ms_longest": [
                    [round(p * 1e3, 3), g] for p, g in top]}


class Context:
    """One run: the cell, its files and the chip."""

    def __init__(self, root, bench, cell, seed, seconds, trace,
                 rehearsal=False, config=None, traffic=None):
        self.root = root
        self.bench = bench
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.rehearsal = rehearsal
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == cell["config"])
        self.config_name = cell["config"]
        self.config = config if config is not None else load_json(
            os.path.join(root, cfg_entry["file"]))
        self.traffic = traffic if traffic is not None else load_json(
            os.path.join(root, "perfbench/traffic",
                         cell["traffic"] + ".json"))
        self.chips = int(cell["chips"])
        self.out_dir = os.path.join(root, ".perfbench_out")
        self.compiles = None
        self.device = None
        self.peaks = None

    def reference(self):
        return load_py(
            os.path.join(self.root, "perfbench/references",
                         self.config_name + ".py"),
            "perfbench_reference_" + self.config_name.replace(".", "_"))

    def log(self, msg):
        sys.stderr.write(f"[perfbench {time.perf_counter() - T_PROCESS_START:7.1f}s] {msg}\n")
        sys.stderr.flush()


def place_caches(root):
    """JAX's persistent compile cache at a fixed path inside the
    checkout (the environment's, where it is set), every program cached
    however short; the program's calibration table in a file of this
    run's own, so that no run changes what the next one does."""
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = os.path.join(root, ".jax_cache")
        os.makedirs(cache, exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    state = os.path.join(root, ".perfbench_out")
    shutil.rmtree(state, ignore_errors=True)
    os.makedirs(state, exist_ok=True)
    os.environ["MXNET_CALIBRATION_CACHE"] = os.path.join(
        state, "calibration.json")
    os.environ.setdefault("MXNET_TELEMETRY_SPANS", "65536")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return cache


def configure_jax():
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def device_record(jax, chips):
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}, devs[:chips]


def peak_bytes(devices):
    """Peak bytes in use on the fullest chip, as the backend reports."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def start_trace(ctx):
    """Begin the capture of the traced sub-window, without the
    profiler's Python tracer: its hook on every Python call slowed the
    serving loop's host turn threefold (PERF.md, PR 25), and no reader
    reads a Python frame. The two sync marks and the program's spans are
    `TraceAnnotation`s, which the host tracer keeps."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(os.path.join(ctx.out_dir, "trace"),
                             profiler_options=opts)


def free_device_memory():
    gc.collect()
    import jax

    jax.clear_caches()
    gc.collect()


def read_per_layer(ctx, facts):
    """Every per-layer metric the cell lists, by its own reader; a
    reader that finds nothing to read returns None and the metric is
    left out of the line."""
    out = {}
    name = ctx.cell["name"]
    e2e_here = {m["name"] for m in ctx.bench["end_to_end"]
                if "workloads" not in m or name in m["workloads"]}
    for m in ctx.bench["per_layer"]:
        if "workloads" in m:
            if name not in m["workloads"]:
                continue
        elif m["moves"] not in e2e_here:
            continue
        path = os.path.join(ctx.root, "perfbench/metrics",
                            m["name"] + ".py")
        reader = load_py(path, "perfbench_metric_"
                         + m["name"].replace(".", "_").replace("-", "_"))
        value = reader.read(facts)
        if value is None:
            continue
        value = float(value)
        if value != value:  # NaN: nothing sound was read
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def emit_result(ctx, res):
    """The counts line, the compared numbers on stderr, and the result
    as the last line of stdout."""
    counts = dict(res.get("counts", {}))
    counts["workload"] = ctx.cell["name"]
    counts["seed"] = ctx.seed
    print(json.dumps({"counts": counts}), flush=True)
    checks = res.get("checks", {})
    sys.stderr.flush()
    sys.stderr.write("compared (value, limit): " + json.dumps(checks)
                     + f"  correct={res['correct']}\n")
    sys.stderr.flush()
    if ctx.rehearsal:
        # off the chip: counts and the check, never a device metric
        metrics = {}
    elif ctx.trace:
        metrics = res["per_layer"]
    else:
        metrics = {}
        name = ctx.cell["name"]
        for m in ctx.bench["end_to_end"]:
            if "workloads" in m and name not in m["workloads"]:
                continue
            if m["name"] in res["end_to_end"]:
                metrics[m["name"]] = {
                    "value": float(res["end_to_end"][m["name"]]),
                    "unit": m["unit"]}
    line = {"correct": bool(res["correct"]),
            "attempted": int(res["attempted"]),
            "failed": int(res["failed"]),
            "metrics": metrics,
            "device": res["device"]}
    if ctx.trace and res.get("breakdown") and not ctx.rehearsal:
        line["breakdown"] = res["breakdown"]
    if ctx.rehearsal:
        line["rehearsal"] = True
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    return line


def build_context(argv, root, rehearsal=False, config=None, traffic=None):
    ap = argparse.ArgumentParser(prog="perfbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"perfbench: no workload {args.workload!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    return Context(root, bench, cells[args.workload], args.seed,
                   args.seconds, args.trace, rehearsal=rehearsal,
                   config=config, traffic=traffic)


def run_cell(ctx, hooks=None):
    """Drive one cell through its kind's runner and print its lines.
    `hooks` is for the self-checks alone: it breaks the timed path
    underneath or asks for the control's reading."""
    jax = configure_jax()
    ctx.compiles = CompileCounter()
    ctx.device, ctx.devices = device_record(jax, ctx.chips)
    if not ctx.rehearsal:
        ctx.peaks = peaks_for(ctx.device["kind"], ctx.root)
    kind = ctx.config["kind"]
    runner = load_py(os.path.join(ctx.root, "perfbench/harness",
                                  f"kind_{kind}.py"),
                     f"perfbench_kind_{kind}")
    res = runner.run(ctx, hooks)
    return emit_result(ctx, res)


def main(argv, root):
    ctx = build_context(argv, root)
    if not os.path.isdir(os.path.join(root, "mxnet_tpu")):
        sys.stderr.write("perfbench: the program (mxnet_tpu/) is not in "
                         "this directory; nothing to measure\n")
        return 2
    place_caches(root)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < ctx.chips:
        sys.stderr.write(
            f"perfbench: {ctx.cell['name']} needs {ctx.chips} TPU chip(s); "
            f"jax found {len(devs)} x {devs[0].platform}. No result: a CPU "
            "run is never written under a device metric's name\n")
        return NO_CHIP_EXIT
    run_cell(ctx)
    return 0
