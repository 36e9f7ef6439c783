"""Evaluation metrics.

Covers the surface of the reference's python/mxnet/metric.py (EvalMetric
hierarchy, registry, composite/custom metrics) with a different core:
every built-in metric is a single vectorized statistic
`stat(label, pred) -> (sum, count)` evaluated over whole batches — no
per-sample Python loops. Predictions are pulled to host once per batch
(the same sync point the reference's `asnumpy()` incurs); the arithmetic
then runs as numpy array expressions.

Device-resident accumulation: the training loop routes updates through
`update_auto` → `update_device`, which evaluates the same statistic's
SUM with jnp ops and appends the DEVICE scalar to a pending list — no
host sync per batch (the instance count is shape arithmetic and lands
in num_inst immediately, so callbacks peeking at num_inst stay
correct). `get()` drains the list with one `jax.device_get` (so the
fetch cost is paid per log interval, not per step) and folds it into
sum_metric in the same order and host precision the per-batch
`update()` path uses — results are identical.
Metrics without a device statistic (custom/numpy fevals, Perplexity,
F1) transparently fall back to host `update()`.
"""
from __future__ import annotations

import numpy as _np

from .ndarray import NDArray


def device_metrics_enabled():
    """Whether the loop-facing `update_auto` routes to the device path
    (MXNET_DEVICE_METRICS, default on)."""
    from . import utils as _utils

    return bool(_utils.getenv("MXNET_DEVICE_METRICS"))


def update_auto(metric, labels, preds):
    """The training/eval loop's metric entry point: device-resident
    accumulation when enabled, the classic per-batch host update
    otherwise (module/{module,executor_group}.py call this)."""
    if device_metrics_enabled():
        metric.update_device(labels, preds)
    else:
        metric.update(labels, preds)


def check_label_shapes(labels, preds, shape=0):
    """Raise when label/pred structure disagrees (list lengths by
    default; array shapes when shape=1)."""
    a = len(labels) if shape == 0 else labels.shape
    b = len(preds) if shape == 0 else preds.shape
    if a != b:
        raise ValueError(
            f"Shape of labels {a} does not match shape of predictions {b}"
        )


def _host(x):
    """Batch array -> host numpy (single device->host pull)."""
    return x.asnumpy() if isinstance(x, NDArray) else _np.asarray(x)


def _device(x):
    """Batch array -> device (jnp) array with no host round-trip."""
    import jax.numpy as jnp

    return x._data if isinstance(x, NDArray) else jnp.asarray(x)


class EvalMetric:
    """Accumulator: running (sum_metric, num_inst) with the reference's
    get()/get_name_value() reporting contract."""

    def __init__(self, name, num=None):
        self.name = name
        self.num = num
        self.reset()

    # device-side mirror of _stat: jnp ops on device arrays returning
    # the device-scalar SUM only (the instance count is pure shape
    # arithmetic — see _count_device — and accumulates on host
    # immediately, so num_inst is current after every update_device).
    # None means "no device path" — the metric accumulates via host
    # update() only.
    _stat_device = None

    # subclasses override ONE of: _stat (vectorized batch statistic) or
    # update (full control)
    def _stat(self, label, pred):
        raise NotImplementedError

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            s, n = self._stat(_host(label), _host(pred))
            self.sum_metric += float(s)
            self.num_inst += int(n)

    def supports_device(self):
        """True when update_device can accumulate without a host sync:
        the metric has a device statistic AND still uses the stock
        update() (a subclass that overrode update() expects its own
        host-side logic to run — honoring that is what keeps the
        fallback 'identical results')."""
        cls = type(self)
        return (self.num is None
                and cls._stat_device is not None
                and cls.update is EvalMetric.update)

    def _device_stat_fn(self):
        """The device statistic as ONE dispatch: jit fuses the handful
        of elementwise/reduce ops per batch into a single launch (the
        eager ops would each pay dispatch overhead on the hot path).
        Shape/dtype changes retrace once and are cached thereafter."""
        fn = getattr(self, "_jit_stat", None)
        if fn is None:
            import jax

            fn = jax.jit(self._stat_device)
            self._jit_stat = fn
        return fn

    def _count_device(self, label, pred):
        """This batch's instance count, from shapes alone (never a
        fetch). Default: one instance per label element."""
        return int(_np.prod(label.shape)) if label.shape else 1

    def update_device(self, labels, preds):
        """Accumulate on device: append this batch's device-scalar sum
        to a pending list, deferring the host fetch to get(); the
        instance count is shape arithmetic and lands in num_inst right
        away. Metrics without a device statistic fall back to the
        per-batch host update() — same results, per-batch sync."""
        if not self.supports_device():
            return self.update(labels, preds)
        check_label_shapes(labels, preds)
        import jax

        fn = self._device_stat_fn()
        for label, pred in zip(labels, preds):
            l, p = _device(label), _device(pred)
            ld, pd = l.devices(), p.devices()
            if ld != pd:
                # the output is committed to its shard's device — or,
                # from a fused mesh step, sharded over the mesh — while
                # the label may live on the default device: co-locate
                # with an async device-to-device copy (no host
                # round-trip), replicated when the output spans a mesh
                if isinstance(p.sharding, jax.sharding.NamedSharding):
                    l = jax.device_put(l, jax.sharding.NamedSharding(
                        p.sharding.mesh, jax.sharding.PartitionSpec()))
                elif len(pd) == 1:
                    l = jax.device_put(l, next(iter(pd)))
            self._pending.append(fn(l, p))
            self.num_inst += self._count_device(label, pred)

    def _drain_pending(self):
        """Fold pending device sums into sum_metric with ONE blocking
        fetch; host-side accumulation order and precision match the
        per-batch update() path exactly (num_inst was already
        accumulated at update_device time)."""
        pending = getattr(self, "_pending", None)
        if not pending:
            return
        self._pending = []
        import jax

        from . import profiler as _profiler

        host = jax.device_get(pending)
        _profiler.count_host_sync("blocking_fetches")
        _profiler.count_host_sync("metric_fetches")
        for s in host:
            self.sum_metric += float(s)

    def reset(self):
        self._pending = []
        if self.num is None:
            self.num_inst, self.sum_metric = 0, 0.0
        else:
            self.num_inst = [0] * self.num
            self.sum_metric = [0.0] * self.num

    def get(self):
        self._drain_pending()
        if self.num is None:
            val = (self.sum_metric / self.num_inst
                   if self.num_inst else float("nan"))
            return (self.name, val)
        return (
            [f"{self.name}_{i}" for i in range(self.num)],
            [s / n if n else float("nan")
             for s, n in zip(self.sum_metric, self.num_inst)],
        )

    def get_name_value(self):
        names, vals = self.get()
        if not isinstance(names, list):
            names, vals = [names], [vals]
        return list(zip(names, vals))

    def __str__(self):
        return f"EvalMetric: {dict(self.get_name_value())}"


# --------------------------------------------------------- classification

def _as_class_ids(label, pred):
    """Reduce a probability matrix to predicted class ids when label is
    id-shaped; flatten both to 1-D int arrays."""
    if pred.shape != label.shape:
        pred = pred.argmax(axis=1)
    return label.astype("int64").ravel(), pred.astype("int64").ravel()


class Accuracy(EvalMetric):
    """Fraction of argmax(pred) == label."""

    def __init__(self):
        super().__init__("accuracy")

    def _stat(self, label, pred):
        y, yhat = _as_class_ids(label, pred)
        check_label_shapes(y, yhat, shape=1)
        return (y == yhat).sum(), y.size

    def _stat_device(self, label, pred):
        import jax.numpy as jnp

        # same reduction as _as_class_ids; int32 ids (x64 is disabled
        # on device) are exact for any realistic class count
        if pred.shape != label.shape:
            pred = jnp.argmax(pred, axis=1)
        y = label.astype(jnp.int32).ravel()
        yhat = pred.astype(jnp.int32).ravel()
        check_label_shapes(y, yhat, shape=1)
        return (y == yhat).sum()


class TopKAccuracy(EvalMetric):
    """Label contained in the k highest-scoring classes. Uses
    argpartition (O(n) per row) rather than a full sort."""

    def __init__(self, **kwargs):
        self.top_k = int(kwargs.get("top_k", 1))
        assert self.top_k > 1, \
            "Please use Accuracy if top_k is no more than 1"
        super().__init__(f"top_k_accuracy_{self.top_k}")

    def _stat(self, label, pred):
        y = label.astype("int64").ravel()
        if pred.ndim == 1:
            return (pred.astype("int64") == y).sum(), y.size
        k = min(self.top_k, pred.shape[1])
        if k == pred.shape[1]:
            top = _np.arange(pred.shape[1])[None, :].repeat(len(y), 0)
        else:
            top = _np.argpartition(-pred, k, axis=1)[:, :k]
        return (top == y[:, None]).any(axis=1).sum(), y.size

    def _stat_device(self, label, pred):
        import jax
        import jax.numpy as jnp

        y = label.astype(jnp.int32).ravel()
        if pred.ndim == 1:
            return (pred.astype(jnp.int32) == y).sum()
        k = min(self.top_k, pred.shape[1])
        if k == pred.shape[1]:
            # every class is in the top-k: all (valid) labels hit
            return jnp.asarray(y.size)
        _, top = jax.lax.top_k(pred, k)
        return (top == y[:, None]).any(axis=1).sum()


class F1(EvalMetric):
    """Binary F1, computed from vectorized TP/FP/FN counts per batch."""

    def __init__(self):
        super().__init__("f1")

    def _stat(self, label, pred):
        check_label_shapes(label, pred)
        y, yhat = _as_class_ids(label, pred)
        if _np.unique(y).size > 2:
            raise ValueError(
                "F1 currently only supports binary classification."
            )
        tp = ((yhat == 1) & (y == 1)).sum()
        fp = ((yhat == 1) & (y == 0)).sum()
        fn = ((yhat == 0) & (y == 1)).sum()
        precision = tp / (tp + fp) if tp + fp else 0.0
        recall = tp / (tp + fn) if tp + fn else 0.0
        f1 = (2 * precision * recall / (precision + recall)
              if precision + recall else 0.0)
        return f1, 1


class CrossEntropy(EvalMetric):
    """Mean negative log-likelihood of the label row."""

    def __init__(self, eps=1e-8):
        super().__init__("cross-entropy")
        self.eps = eps

    def _stat(self, label, pred):
        y = label.ravel().astype("int64")
        assert y.shape[0] == pred.shape[0]
        picked = pred[_np.arange(y.size), y]
        return -_np.log(picked + self.eps).sum(), y.size

    def _stat_device(self, label, pred):
        import jax.numpy as jnp

        y = label.ravel().astype(jnp.int32)
        assert y.shape[0] == pred.shape[0]
        picked = pred[jnp.arange(y.shape[0]), y]
        return -jnp.log(picked + self.eps).sum()


class Perplexity(EvalMetric):
    """exp(mean NLL) with an optional ignored label id. One perplexity
    value is accumulated per update() call, matching the reference."""

    def __init__(self, ignore_label, axis=-1):
        super().__init__("Perplexity")
        self.ignore_label = ignore_label
        self.axis = axis

    def update(self, labels, preds):
        assert len(labels) == len(preds)
        nll, count = 0.0, 0
        for label, pred in zip(labels, preds):
            label, pred = _host(label), _host(pred)
            classes = pred.shape[-1]
            assert label.size == pred.size // classes, \
                f"shape mismatch: {label.shape} vs. {pred.shape}"
            y = label.ravel().astype("int64")
            p = pred.reshape(-1, classes)[_np.arange(y.size), y]
            keep = _np.ones_like(p, dtype=bool)
            if self.ignore_label is not None:
                keep = y != self.ignore_label
            nll -= _np.log(_np.maximum(p[keep], 1e-10)).sum()
            count += int(keep.sum())
        self.sum_metric += (_np.exp(nll / count) if count
                            else float("nan"))
        self.num_inst += 1


# ------------------------------------------------------------ regression

class _Regression(EvalMetric):
    """Shared shape handling for elementwise-error metrics; one value
    accumulated per batch."""

    def _error(self, diff):
        raise NotImplementedError

    def _error_device(self, diff):
        raise NotImplementedError

    def supports_device(self):
        # a user subclass defining only the host _error stays on the
        # host path instead of hitting NotImplementedError mid-epoch
        return (super().supports_device()
                and type(self)._error_device
                is not _Regression._error_device)

    @staticmethod
    def _align(label, pred):
        # align shapes: same-size arrays compare ELEMENTWISE (a (N,)
        # label against (N,) or (N,1) preds must never broadcast to an
        # (N,N) outer difference); a per-sample (N,) label against
        # multi-column (N,M) preds broadcasts across columns (the
        # reference regression-metric convention)
        if label.shape != pred.shape:
            squeezed = tuple(s for s in label.shape if s != 1)
            p_squeezed = tuple(s for s in pred.shape if s != 1)
            if squeezed == p_squeezed:
                # singleton-axis differences only ((N,) vs (N,1)):
                # genuinely the same elements, align them
                label = label.reshape(pred.shape)
            elif (label.ndim == 1 and pred.ndim > 1
                  and label.shape[0] == pred.shape[0]):
                label = label.reshape(-1, *([1] * (pred.ndim - 1)))
            else:
                raise ValueError(
                    f"regression metric: label shape {label.shape} "
                    f"incompatible with pred shape {pred.shape}")
        return label

    def _stat(self, label, pred):
        label = self._align(label, pred)
        return self._error(label - pred), 1

    def _stat_device(self, label, pred):
        label = self._align(label, pred)
        return self._error_device(label - pred)

    def _count_device(self, label, pred):
        return 1  # one value per batch, like _stat


class MAE(_Regression):
    def __init__(self):
        super().__init__("mae")

    def _error(self, diff):
        return _np.abs(diff).mean()

    def _error_device(self, diff):
        import jax.numpy as jnp

        return jnp.abs(diff).mean()


class MSE(_Regression):
    def __init__(self):
        super().__init__("mse")

    def _error(self, diff):
        return _np.square(diff).mean()

    def _error_device(self, diff):
        import jax.numpy as jnp

        return jnp.square(diff).mean()


class RMSE(_Regression):
    def __init__(self):
        super().__init__("rmse")

    def _error(self, diff):
        return _np.sqrt(_np.square(diff).mean())

    def _error_device(self, diff):
        import jax.numpy as jnp

        return jnp.sqrt(jnp.square(diff).mean())


# ----------------------------------------------------- loss passthrough

class Loss(EvalMetric):
    """Mean of raw outputs — for MakeLoss-style heads. Ignores labels."""

    def __init__(self, name="loss"):
        super().__init__(name)

    def update(self, _labels, preds):
        for pred in preds:
            p = _host(pred)
            self.sum_metric += float(p.sum())
            self.num_inst += p.size

    def update_device(self, _labels, preds):
        for pred in preds:
            p = _device(pred)
            self._pending.append(p.sum())
            self.num_inst += p.size


class Torch(Loss):
    def __init__(self):
        super().__init__("torch")


class Caffe(Loss):
    def __init__(self):
        super().__init__("caffe")


# --------------------------------------------------- composite / custom

class CompositeEvalMetric(EvalMetric):
    """Fan updates out to child metrics; reports them all."""

    def __init__(self, **kwargs):
        super().__init__("composite")
        self.metrics = list(kwargs.get("metrics", []))

    def add(self, metric):
        self.metrics.append(create(metric))

    def get_metric(self, index):
        try:
            return self.metrics[index]
        except IndexError:
            raise ValueError(
                f"Metric index {index} is out of range 0 and "
                f"{len(self.metrics)}"
            )

    def update(self, labels, preds):
        for m in self.metrics:
            m.update(labels, preds)

    def update_device(self, labels, preds):
        for m in self.metrics:
            m.update_device(labels, preds)

    def reset(self):
        self._pending = []
        for m in getattr(self, "metrics", []):
            m.reset()

    def get(self):
        pairs = [m.get() for m in self.metrics]
        return ([n for n, _ in pairs], [v for _, v in pairs])


class CustomMetric(EvalMetric):
    """Wrap feval(label, pred) -> value or (sum, count)."""

    def __init__(self, feval, name=None, allow_extra_outputs=False):
        if name is None:
            name = feval.__name__
            if "<" in name:
                name = f"custom({name})"
        super().__init__(name)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            check_label_shapes(labels, preds)
        for pred, label in zip(preds, labels):
            out = self._feval(_host(label), _host(pred))
            if isinstance(out, tuple):
                s, n = out
            else:
                s, n = out, 1
            self.sum_metric += s
            self.num_inst += n


def np(numpy_feval, name=None, allow_extra_outputs=False):
    """CustomMetric from a numpy feval."""

    def feval(label, pred):
        return numpy_feval(label, pred)

    feval.__name__ = numpy_feval.__name__
    return CustomMetric(feval, name, allow_extra_outputs)


_REGISTRY = {
    "acc": Accuracy,
    "accuracy": Accuracy,
    "ce": CrossEntropy,
    "f1": F1,
    "mae": MAE,
    "mse": MSE,
    "rmse": RMSE,
    "top_k_accuracy": TopKAccuracy,
    "perplexity": Perplexity,
    "loss": Loss,
    "torch": Torch,
    "caffe": Caffe,
}


def create(metric, **kwargs):
    """Resolve a metric from a name, callable, instance, or list."""
    if callable(metric):
        return CustomMetric(metric)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, list):
        out = CompositeEvalMetric()
        for child in metric:
            out.add(create(child, **kwargs))
        return out
    try:
        return _REGISTRY[metric.lower()](**kwargs)
    except Exception:
        raise ValueError(
            f"Metric must be either callable or in {sorted(_REGISTRY)}"
        )
