"""BaseModule: the abstract training-API contract + the `fit` loop.

Covers the surface of the reference's python/mxnet/module/base_module.py
(fit/score/predict/forward_backward and the abstract method set). The
epoch loop is host-side control flow; on TPU each forward_backward+update
is ONE fused XLA computation (executor.py / parallel/dp_step.py), so the
loop body is a handful of device launches — the logical endpoint of the
reference's bulk-exec segments.
"""
from __future__ import annotations

import collections
import logging
import time

from .. import metric as _metric
from .. import ndarray as nd
from .. import profiler as _profiler
from .. import utils as _utils
from ..telemetry import http as _thttp
from ..telemetry import trace as _trace
from ..callback import BatchEndParam
from ..initializer import Uniform


def _as_list(obj):
    return obj if isinstance(obj, list) else [obj]


class _DispatchWindow:
    """Bounded window of in-flight dispatched training steps.

    fit dispatches step N+1 (device_put + launch) while step N still
    runs, keeping the device fed; to bound HBM (each in-flight step
    holds its batch + activations) the window retains at most K step
    fences — device arrays that complete no earlier than their step —
    and blocks on the oldest before admitting another. K=0 degenerates
    to the synchronous pre-pipelined loop. Waits are recorded in
    profiler hostSyncStats (dispatch_stalls / stall_time_us)."""

    def __init__(self, max_in_flight):
        self.k = max(0, int(max_in_flight))
        self._fences = collections.deque()

    def admit(self, fence):
        """Fence the step just dispatched; waits until fewer than K
        older steps remain in flight."""
        if fence is None:
            return
        if self.k <= 0:
            self._wait(fence)
            return
        while len(self._fences) >= self.k:
            self._wait(self._fences.popleft())
        self._fences.append(fence)
        _profiler.note_steps_in_flight(len(self._fences))

    def drain(self):
        """Epoch boundary / eval: wait out every in-flight step."""
        while self._fences:
            self._wait(self._fences.popleft())

    def _wait(self, fence):
        import jax
        import numpy as _np

        with _trace.span("fit.window_wait") as wait:
            t0 = time.perf_counter()
            jax.block_until_ready(fence)
            t_ready = time.perf_counter()
            # then a one-scalar value fetch: a value on the host is a
            # fence whatever block_until_ready acknowledges (same idiom
            # as Module.sync). Counts as a window stall, not a blocking
            # fetch — no payload crosses. Its own share of the wait is
            # the span's `fetch_us`: the fetch is two tiny programs
            # that queue behind every step already dispatched.
            _np.asarray(jax.device_get(fence.ravel()[0]))
            t1 = time.perf_counter()
            wait.note(fetch_us=round((t1 - t_ready) * 1e6, 1))
        _profiler.note_dispatch_stall(t1 - t0)


def _fire(callbacks, **kwargs):
    """Invoke one-or-many BatchEndParam-style callbacks."""
    if callbacks is None:
        return
    param = BatchEndParam(**kwargs)
    for cb in _as_list(callbacks):
        cb(param)


def _check_input_names(symbol, names, typename, throw):
    """Verify user-declared input names exist among the symbol's
    arguments; suggest the non-parameter ones on mismatch."""
    args = symbol.list_arguments()
    param_suffixes = ("_weight", "_bias", "_gamma", "_beta")
    for name in names:
        if name in args:
            continue
        inputs = [a for a in args if not a.endswith(param_suffixes)]
        msg = (
            f"\033[91mYou created Module with Module(..., {typename}_names="
            f"{names}) but input with name '{name}' is not found in "
            f"symbol.list_arguments(). Did you mean one of:\n\t%s\033[0m"
            % "\n\t".join(inputs)
        )
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


class BaseModule(object):
    """Abstract module: bind -> init_params -> init_optimizer ->
    (forward_backward, update)* with score/predict on top."""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None

    # ------------------------------------------------------ high level
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def _eval_batches(self, eval_data, num_batch, reset):
        """Yield (nbatch, batch) running eval forward on each."""
        if reset:
            eval_data.reset()
        for nbatch, batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                return
            self.forward(batch, is_train=False)
            yield nbatch, batch

    def _unpadded_outputs(self, batch):
        """Current outputs with the batch's pad rows dropped."""
        keep = lambda out: nd.NDArray(
            out._data[: out.shape[0] - batch.pad], ctx=out.context
        )
        return [keep(out) for out in self.get_outputs()]

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None,
              reset=True, epoch=0):
        """Evaluate eval_metric over eval_data."""
        assert self.binded and self.params_initialized
        eval_metric = _metric.create(eval_metric) \
            if not isinstance(eval_metric, _metric.EvalMetric) \
            else eval_metric
        eval_metric.reset()

        seen = 0
        for nbatch, batch in self._eval_batches(eval_data, num_batch,
                                                reset):
            self.update_metric(eval_metric, batch.label)
            _fire(batch_end_callback, epoch=epoch, nbatch=nbatch,
                  eval_metric=eval_metric, locals=locals())
            seen += 1
        _fire(score_end_callback, epoch=epoch, nbatch=seen,
              eval_metric=eval_metric, locals=locals())
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """Yield (outputs, nbatch, batch) per eval batch."""
        assert self.binded and self.params_initialized
        for nbatch, batch in self._eval_batches(eval_data, num_batch,
                                                reset):
            yield self._unpadded_outputs(batch), nbatch, batch

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Forward over eval_data collecting outputs; merged along the
        batch axis unless merge_batches=False."""
        assert self.binded and self.params_initialized
        collected = [
            self._unpadded_outputs(batch)
            for _, batch in self._eval_batches(eval_data, num_batch,
                                               reset)
        ]
        if not collected:
            return collected
        if not merge_batches:
            return collected

        width = len(collected[0])
        if any(len(outs) != width for outs in collected):
            raise ValueError(
                "Cannot merge batches: output count varies across "
                "mini-batches (bucketing?)")
        merged = [
            nd.concatenate([outs[i] for outs in collected])
            for i in range(width)
        ]
        if width == 1 and not always_output_list:
            return merged[0]
        return merged

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None,
            kvstore="local", optimizer="sgd",
            optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, steps_per_dispatch=1, numerics=None):
        """The training driver: bind + init, then the epoch loop of
        forward_backward/update/metrics/callbacks/eval.

        `numerics` opts into run-health observability
        (mxnet_tpu.numerics): pass a NumericsMonitor (or True for
        defaults; MXNET_NUMERICS=1 enables it ambiently). A sentinel
        stats row rides inside every fused step and is drained in one
        fetch per interval — norms/anomaly rules/run log with no new
        per-step host syncs.

        steps_per_dispatch > 1 (opt-in) stacks that many iterator
        batches on a leading axis and advances them through ONE
        device dispatch (Module.run_steps: a compiled lax.scan step
        loop) — the per-dispatch host cost amortizes k-fold. Training
        math is identical to k sequential steps; the OBSERVATION
        cadence coarsens: the train metric and batch_end_callback see
        only the last batch of each k-group (outputs of the inner
        steps are not materialized), and a monitor forces the
        single-step path. Epoch remainders smaller than k run
        single-step."""
        if num_epoch is None:
            raise ValueError("please specify number of epochs")

        # opt-in live introspection of a training run: with
        # MXNET_TELEMETRY_PORT set, /metrics + /statusz answer mid-fit
        _thttp.maybe_start_exporter()

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params,
                         allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)

        from .. import numerics as _numerics  # local: keep fit import-light

        num_mon = _numerics.from_fit_arg(numerics, logger=self.logger)
        if num_mon is not None:
            num_mon.attach(self)
            if not num_mon.active:
                num_mon = None

        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)

        k = int(steps_per_dispatch)
        use_k = (k > 1 and monitor is None
                 and hasattr(self, "run_steps")
                 and getattr(self, "_fused_step", None) is not None)
        if k > 1 and not use_k:
            self.logger.warning(
                "fit: steps_per_dispatch=%d ignored (monitor installed "
                "or no fused train path) — using the per-batch loop", k)

        # dispatch-ahead: keep up to K steps in flight so batch N+1's
        # staging overlaps step N's device time (MXNET_DISPATCH_AHEAD;
        # 0 = synchronous). Metric updates are device-resident on this
        # path (metric.update_auto), so nothing below blocks per step.
        window = _DispatchWindow(_utils.getenv("MXNET_DISPATCH_AHEAD"))

        def train_one(epoch, nbatch, batch):
            if monitor is not None:
                monitor.tic()
            if num_mon is not None:
                num_mon.note_batch(batch)
            # fit.dispatch is partitioned by four leaves: fit.stage
            # (forward_backward: on the fused path it only turns the
            # batch into device arrays; the eager executors launch
            # forward and backward here), fit.launch (update: the step
            # program's call returning), fit.metric, and
            # fit.window_wait inside the window's _wait
            tid = f"fit-e{epoch}-b{nbatch}"
            with _trace.span("fit.dispatch", trace_id=tid):
                with _trace.span("fit.stage", trace_id=tid):
                    self.forward_backward(batch)
                with _trace.span("fit.launch", trace_id=tid):
                    self.update()
                with _trace.span("fit.metric", trace_id=tid):
                    self.update_metric(eval_metric, batch.label)
                window.admit(self._step_fence())
            if monitor is not None:
                monitor.toc_print()
            if num_mon is not None:
                num_mon.after_batch(self, epoch, nbatch)
            _fire(batch_end_callback, epoch=epoch, nbatch=nbatch,
                  eval_metric=eval_metric, locals=locals())

        def train_group(epoch, nbatch, group):
            import jax.numpy as jnp

            from .. import io as _io  # local: io imports module too

            def shape_of(arr):
                return tuple(getattr(arr, "shape", ()))

            first = group[0]
            if any(
                shape_of(b.data[i]) != shape_of(first.data[i])
                for b in group for i in range(len(first.data))
            ) or any(
                shape_of(b.label[i]) != shape_of(first.label[i])
                for b in group
                for i in range(len(first.label or []))
            ):
                # variable-shape batches (e.g. a bucketing iterator):
                # can't stack — train this group per batch
                for off, b in enumerate(group):
                    train_one(epoch, nbatch - len(group) + 1 + off, b)
                return

            def stack(arrs):
                # stay on device: no asnumpy round-trip on the hot path
                return nd.NDArray(jnp.stack([
                    a._data if isinstance(a, nd.NDArray)
                    else jnp.asarray(a) for a in arrs]))

            stacked = _io.DataBatch(
                data=[stack([b.data[i] for b in group])
                      for i in range(len(group[0].data))],
                label=[stack([b.label[i] for b in group])
                       for i in range(len(group[0].label or []))],
            )
            if num_mon is not None:
                num_mon.note_batch(group[-1])
            tid = f"fit-e{epoch}-b{nbatch}"
            with _trace.span("fit.dispatch", trace_id=tid,
                             steps=len(group)):
                with _trace.span("fit.launch", trace_id=tid):
                    self.run_steps(stacked, len(group), stacked=True)
                last = group[-1]
                with _trace.span("fit.metric", trace_id=tid):
                    self.update_metric(eval_metric, last.label)
                window.admit(self._step_fence())
            if num_mon is not None:
                num_mon.after_batch(self, epoch, nbatch)
            _fire(batch_end_callback, epoch=epoch, nbatch=nbatch,
                  eval_metric=eval_metric, locals=locals())

        try:
            self._fit_epochs(
                train_data, eval_data, begin_epoch, num_epoch,
                eval_metric, validation_metric, use_k, k, window,
                train_one, train_group, num_mon,
                epoch_end_callback, eval_end_callback,
                eval_batch_end_callback)
        finally:
            if num_mon is not None:
                # crash-path flush: whatever killed the loop, the rows
                # already computed on device ARE the evidence — drain
                # them blocking and seal the run log before the
                # exception propagates (a no-op fetch-wise when the
                # epoch-boundary drain already emptied the queue)
                try:
                    num_mon.drain(self)
                finally:
                    num_mon.close()

    def _fit_epochs(self, train_data, eval_data, begin_epoch, num_epoch,
                    eval_metric, validation_metric, use_k, k, window,
                    train_one, train_group, num_mon,
                    epoch_end_callback, eval_end_callback,
                    eval_batch_end_callback):
        """fit's epoch loop, split out so fit can guarantee the
        numerics drain/close on ANY exit path."""
        for epoch in range(begin_epoch, num_epoch):
            # pin epoch-keyed iterators (mxnet_tpu.data loaders, seeded
            # NDArrayIter) to THIS epoch's permutation: a no-op when
            # already there, so a mid-epoch resume keeps its position
            if hasattr(train_data, "set_epoch"):
                train_data.set_epoch(epoch)
            started = time.time()
            eval_metric.reset()

            # manual iteration so the time BLOCKED on the input
            # pipeline is its own span (fit.data_wait), distinct from
            # the dispatch span train_one/train_group record
            def fetch_batches(epoch=epoch):
                it = iter(train_data)
                nfetch = 0
                while True:
                    try:
                        with _trace.span(
                                "fit.data_wait",
                                trace_id=f"fit-e{epoch}-b{nfetch}"):
                            batch = next(it)
                    except StopIteration:
                        return
                    yield batch
                    nfetch += 1

            nbatch = -1
            if not use_k:
                for nbatch, batch in enumerate(fetch_batches()):
                    train_one(epoch, nbatch, batch)
            else:
                # nbatch counts COMPLETED batches (so count-based
                # callbacks like Speedometer keep firing: after m
                # groups nbatch = m*k, which hits any frequency)
                nbatch = 0
                group = []
                for batch in fetch_batches():
                    group.append(batch)
                    if len(group) == k:
                        nbatch += k
                        train_group(epoch, nbatch, group)
                        group = []
                for batch in group:   # epoch remainder: single steps
                    nbatch += 1
                    train_one(epoch, nbatch, batch)

            # epoch boundary: nothing may stay in flight across the
            # metric fetch, param snapshot, or eval below
            with _trace.span("fit.metric_drain",
                             trace_id=f"fit-e{epoch}"):
                window.drain()
                name_vals = eval_metric.get_name_value()
            if num_mon is not None:
                # epoch-boundary drain: catches the tail of rows the
                # interval missed and stamps the epoch marker; a no-op
                # fetch-wise when the interval already drained them
                num_mon.drain(self, epoch=epoch,
                              metrics=dict(name_vals))

            for name, val in name_vals:
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name,
                                 val)
            epoch_seconds = time.time() - started
            self.logger.info("Epoch[%d] Time cost=%.3f", epoch,
                             epoch_seconds)
            # measured-cost calibration (profiling): the epoch's mean
            # step time is a free steady-state measurement — everything
            # in flight just drained, so the wall time is honest
            self._harvest_fit_calibration(
                epoch_seconds,
                nbatch if use_k else nbatch + 1)

            # surface trained values to the module-level dicts (and any
            # epoch callbacks — checkpointing reads these)
            args, auxs = self.get_params()
            self.set_params(args, auxs)
            if epoch_end_callback is not None:
                for cb in _as_list(epoch_end_callback):
                    cb(epoch, self.symbol, args, auxs)

            if eval_data:
                res = self.score(
                    eval_data, validation_metric,
                    score_end_callback=eval_end_callback,
                    batch_end_callback=eval_batch_end_callback,
                    epoch=epoch)
                for name, val in res:
                    self.logger.info("Epoch[%d] Validation-%s=%f",
                                     epoch, name, val)

            train_data.reset()

    def _harvest_fit_calibration(self, epoch_seconds, steps):
        """Record the epoch's mean step seconds into the profiling
        CalibrationStore under this module's canonical graph digest
        (kind "fit_step") — ROADMAP item 2's measured record, taken
        where the framework already timed the epoch. Advisory: any
        failure (no symbol, no digest) is silent."""
        if steps <= 0 or epoch_seconds <= 0:
            return
        try:
            from .. import profiling as _profiling

            if not _profiling.profiling_enabled():
                return
            digest = getattr(self, "_fit_calibration_digest", None)
            if digest is None:
                sym = getattr(self, "symbol", None)
                if sym is None:
                    return
                digest = sym.canonical_signature()
                self._fit_calibration_digest = digest
            import jax

            _profiling.calibration_store().record(
                digest, jax.default_backend(), "fit_step",
                epoch_seconds / steps,
                meta={"steps": int(steps)})
        except Exception:
            pass

    # ------------------------------------------------------ parameters
    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False,
                    force_init=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params,
                         allow_missing=allow_missing,
                         force_init=force_init)

    def save_params(self, fname):
        """Serialize arg/aux params with the reference's arg:/aux: key
        tags (format compatibility)."""
        args, auxs = self.get_params()
        tagged = {f"arg:{k}": v for k, v in args.items()}
        tagged.update({f"aux:{k}": v for k, v in auxs.items()})
        nd.save(fname, tagged)

    def load_params(self, fname):
        """Inverse of save_params."""
        split = {"arg": {}, "aux": {}}
        for key, value in nd.load(fname).items():
            kind, _, name = key.partition(":")
            if kind not in split:
                raise ValueError(f"Invalid param file {fname}")
            split[kind][name] = value
        self.set_params(split["arg"], split["aux"])

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        assert not merge_multi_context
        return []

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized
        assert not states and not value

    def install_monitor(self, mon):
        raise NotImplementedError()

    # ----------------------------------------------------- computation
    def prepare(self, data_batch):
        pass

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def _step_fence(self):
        """Device array completing no earlier than the last dispatched
        step, for fit's dispatch-ahead window; None disables windowing
        for modules without a device-side step."""
        return None

    # --------------------------------------------------------- binding
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False,
             shared_module=None, grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError()

    # ------------------------------------------------------ properties
    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    @property
    def symbol(self):
        return self._symbol
