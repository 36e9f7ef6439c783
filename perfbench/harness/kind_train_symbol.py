"""Runner for configurations of kind `train_symbol`: a Symbol model
trained through ONE `Module.fit` call.

The fit call has three epochs, all fed by one iterator of
device-resident batches:
  epoch 0  the first four steps from the seed's weights, on batches
           whose rows all differ; the losses of steps 1-3 and the
           parameters after steps 1 and 3 are read here (set-up)
  epoch 1  a few warm steps, so that the epoch boundary's own work
           (drain, metric fetch, get_params/set_params) has run once
  epoch 2  THE WINDOW: the iterator fences (`Module.sync`), starts the
           clock, feeds steps until `--seconds` have passed, fences
           again after the last step has left the device, stops the
           clock and ends the epoch. No epoch end, metric fetch,
           get_params or eval lies between the two fences.
`train_throughput` = images of the steps fed in the window over the
seconds between the fences. The reference follows steps 1-3 after the
window has closed and the program's state is freed.
"""
import os
import time

import numpy as np

from perfbench.harness import check, common, trace_reduce

CHECK_STEPS = 3      # compared; the program drives one more in epoch 0
now = time.perf_counter


def _contexts(mx, chips):
    return [mx.tpu(i) for i in range(chips)] if chips > 1 else mx.tpu()


def _decays(name):
    return name.endswith("_weight") or name.endswith("_gamma")


class WindowIter:
    """The synthetic iterator: batches already on the device; epoch 2
    is the fenced window."""

    def __init__(self, mx, ctx, pool, batch_shape, order):
        self.ctx = ctx
        self.mod = None
        self.batch_size = batch_shape[0]
        self.provide_data = [mx.io.DataDesc("data", batch_shape,
                                            layout="NHWC")]
        self.provide_label = [mx.io.DataDesc("softmax_label",
                                             (batch_shape[0],))]
        self.batches = [mx.io.DataBatch(data=[mx.nd.NDArray(x)],
                                        label=[mx.nd.NDArray(y)], pad=0)
                        for x, y in pool]
        self.order = order
        self.warm_steps = int(ctx.traffic["warmup_steps"])
        self.trace_seconds = float(ctx.traffic.get("trace_seconds", 5.0))
        self.epoch = 0
        self.i = 0
        self.t0 = self.t1 = None
        self.steps = 0
        self.built0 = None
        self.built1 = None
        self.tracing = False
        self.t_open = self.t_close = None
        self.open_step = self.close_step = None
        self._since_start = 0
        self.gc = common.GcWatch()
        self.gc_counts = {}

    def set_epoch(self, epoch):
        self.epoch = epoch
        self.i = 0

    def reset(self):
        self.i = 0

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def next(self):
        if self.epoch == 0:
            if self.i >= CHECK_STEPS + 1:
                raise StopIteration
            b = self.batches[self.i % len(self.batches)]
        elif self.epoch == 1:
            if self.i >= self.warm_steps:
                raise StopIteration
            b = self.batches[self.order[self.i % len(self.order)]]
        else:
            b = self._window_next()
        self.i += 1
        return b

    def _window_next(self):
        import jax

        ctx = self.ctx
        if self.t0 is None:
            from mxnet_tpu.telemetry import trace as spans

            self.gc.open()
            self.mod.sync()                       # fence 1
            spans.clear()
            self.built0 = ctx.compiles.built()
            self.t0 = now()
        t = now()
        if ctx.trace:
            start_at = self.t0 + max(
                0.0, ctx.seconds - self.trace_seconds - 1.0)
            if not self.tracing and t >= start_at:
                common.start_trace(ctx)
                self.tracing = True
            elif self.tracing and self.t_open is None:
                self._since_start += 1
                if self._since_start >= 4:
                    with jax.profiler.TraceAnnotation(trace_reduce.SYNC_A):
                        self.t_open = now()
                    self.open_step = self.steps
        if t - self.t0 >= ctx.seconds:
            if self.t_open is not None:
                with jax.profiler.TraceAnnotation(trace_reduce.SYNC_B):
                    self.t_close = now()
                self.close_step = self.steps
            self.mod.sync()                       # fence 2
            self.t1 = now()
            self.gc_counts = self.gc.close()
            self.built1 = ctx.compiles.built()
            raise StopIteration
        self.steps += 1
        return self.batches[self.order[self.steps % len(self.order)]]


def build_and_fit(ctx, mx, ref, hooks=None):
    """Set-up and the window: returns what the check and the metrics
    need. `hooks` lets the self-check break the timed path underneath
    (a dict of callables, see selfcheck/)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mxnet_tpu.models import get_resnet

    cfg, tr = ctx.config, ctx.traffic
    batch = int(tr["batch_per_chip"]) * ctx.chips
    side = int(cfg["image_size"])
    contexts = _contexts(mx, ctx.chips)
    sharding = None
    if ctx.chips > 1:
        devs = [c.jax_device() for c in contexts]
        sharding = NamedSharding(Mesh(np.asarray(devs), ("data",)),
                                 P("data"))
    w0 = ref.make_params(ctx.seed, cfg)
    pool = ref.make_batches(ctx.seed, cfg, batch,
                            int(tr["distinct_batches"]), sharding)
    order = list(np.random.RandomState(ctx.seed % (2 ** 32)).permutation(
        len(pool)))
    net = get_resnet(num_classes=int(cfg["num_classes"]),
                     num_layers=int(cfg["num_layers"]),
                     image_shape=(3, side, side), layout=cfg["layout"],
                     stem=cfg["stem"])
    mod = mx.mod.Module(net, context=contexts)
    mod.cast_compute(jnp.dtype(cfg["compute_dtype"]))
    it = WindowIter(mx, ctx, pool, (batch, side, side, 3), order)
    it.mod = mod
    got = {"losses": [], "w1": None, "w3": None, "probs1": None}

    def fetch_params():
        args, _ = mod.get_params()
        return {k: np.asarray(v._data) for k, v in args.items()}

    def on_batch(param):
        if param.epoch != 0 or param.nbatch >= CHECK_STEPS:
            return
        n = param.nbatch
        probs = np.asarray(mod.get_outputs()[0]._data, np.float64)
        labels = np.asarray(pool[n][1]).astype(np.int64)
        picked = probs[np.arange(len(labels)), labels]
        got["losses"].append(float(-np.mean(np.log(picked + 1e-8))))
        if n == 0:
            got["probs1"] = probs
            got["w1"] = fetch_params()
        elif n == CHECK_STEPS - 1:
            got["w3"] = fetch_params()

    aux = {}
    for name, shape in ref.aux_shapes(cfg).items():
        fill = jnp.ones if name.endswith("_var") else jnp.zeros
        aux[name] = mx.nd.NDArray(fill(shape, jnp.float32))
    hyper = cfg["optimizer"]
    if hooks and "before_fit" in hooks:
        hooks["before_fit"](mod)
    mod.fit(it, eval_metric=mx.metric.CrossEntropy(),
            kvstore=tr["kvstore"], optimizer="sgd",
            optimizer_params=(("learning_rate", hyper["learning_rate"]),
                              ("momentum", hyper["momentum"]),
                              ("wd", hyper["wd"])),
            arg_params={k: mx.nd.NDArray(v) for k, v in w0.items()},
            aux_params=aux, initializer=None, num_epoch=3,
            batch_end_callback=on_batch,
            steps_per_dispatch=int(tr["steps_per_dispatch"]))
    return {"mod": mod, "it": it, "w0": w0, "pool": pool, "got": got,
            "batch": batch, "sharding": sharding}


def window_spans():
    from mxnet_tpu.telemetry import trace as spans

    return [(s.name, s.t0, s.t1, s.attrs) for s in spans.recent_spans()]


def run_reference(ctx, ref, st, **kw):
    """The reference over the first three steps, on the chips the cell
    uses (the batch sharded over them as the program shards it, the
    arithmetic that of the whole batch)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    w0 = st["w0"]
    if st["sharding"] is not None:
        repl = NamedSharding(st["sharding"].mesh, P())
        w0 = {k: jax.device_put(v, repl) for k, v in w0.items()}
    kw.setdefault("compute", jnp.dtype(ctx.config["compute_dtype"]))
    return ref.train_steps(
        w0, st["pool"][:CHECK_STEPS], ctx.config, ctx.config["optimizer"],
        **kw)


def run(ctx, hooks=None):
    import jax

    import mxnet_tpu as mx

    ref = ctx.reference()
    st = build_and_fit(ctx, mx, ref, hooks)
    it, mod = st["it"], st["mod"]
    hlo_labels = None
    if it.tracing:
        jax.profiler.stop_trace()
        compiled = getattr(mod._fused_step, "_compiled", None)
        if compiled:
            hlo_labels = trace_reduce.labels_from_hlo(compiled.as_text())
    spans = window_spans()
    in_win = [s for s in spans if s[1] >= it.t0 and s[2] <= it.t1]
    disp = sorted((s[2] - s[1]) * 1e3 for s in in_win
                  if s[0] == "fit.dispatch")
    fenced = it.t1 - it.t0
    rate = it.steps * st["batch"] / fenced
    peak = common.peak_bytes(ctx.devices)
    ctx.log(f"memory_stats: {ctx.devices[0].memory_stats()}")
    counts = {
        "steps": it.steps, "fenced_seconds": fenced,
        "seconds_asked": ctx.seconds,
        "compilations_in_window": it.built1 - it.built0,
        "epoch_ends_in_window": sum(1 for s in in_win
                                    if s[0] == "fit.metric_drain"),
        "peak_bytes": peak,
        "dispatch_ms_median": disp[len(disp) // 2] if disp else None,
        "dispatch_ms_longest": [round(d, 3) for d in disp[-5:][::-1]],
        "train_throughput": rate,
        "setup_s": it.t0 - common.T_PROCESS_START,
    }
    counts.update(it.gc_counts)
    ctx.log("window: " + str(counts))
    w0_host = {k: np.asarray(v) for k, v in st["w0"].items()}
    got = st["got"]
    # free the program's state before the reference runs
    st["mod"] = st["it"] = None
    it.mod = None
    it.batches = None
    del mod
    st["pool"] = st["pool"][:CHECK_STEPS]
    common.free_device_memory()

    t_ref = now()
    want = run_reference(ctx, ref, st)
    numbers, where = check.training_numbers(
        got, want, w0_host, ctx.config["optimizer"], _decays)
    checks, ok = check.judge(numbers, ctx.config["check"]["limits"])
    ok = ok and counts["compilations_in_window"] == 0 \
        and counts["epoch_ends_in_window"] == 0
    ctx.log(f"reference: {now() - t_ref:.1f}s; every number read "
            f"{ {k: round(v, 5) for k, v in numbers.items()} }; worst "
            f"leaves {where['grad1_leaf']}, {where['change3_leaf']}")

    res = {"correct": ok, "attempted": it.steps, "failed": 0,
           "counts": counts, "checks": checks,
           "end_to_end": {"train_throughput": rate,
                          "setup_s": counts["setup_s"]},
           "device": dict(ctx.device, memory_peak_bytes=peak)}
    if ctx.trace:
        raw = trace_reduce.load_xplane(
            os.path.join(ctx.out_dir, "trace"), hlo_labels) \
            if it.t_open is not None and it.t_close is not None else None
        red = trace_reduce.Reduced(raw, spans, it.t_open, it.t_close)
        if not red.ok:
            raise RuntimeError("the traced window holds no device "
                               "operation: nothing to reduce")
        facts = {"config": ctx.config, "chips": ctx.chips,
                 "batch": st["batch"], "peaks": ctx.peaks, "trace": red,
                 "spans": spans, "window_host": (it.t_open, it.t_close),
                 "window_steps": it.close_step - it.open_step}
        res["per_layer"] = common.read_per_layer(ctx, facts)
        for k, v in facts.get("notes", {}).items():
            ctx.log(f"{k}: {v}")
        res["breakdown"] = red.breakdown()
        res["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        ctx.log(f"trace: window {red.window_s:.3f}s busy {red.busy_s:.3f}s "
                f"clock drift {red.drift * 1e3:.3f}ms")
    return res
