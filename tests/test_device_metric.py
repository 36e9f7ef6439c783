"""Device-resident metric accumulation (metric.update_device).

Parity contract: for every metric with a device statistic, accumulating
via update_device and fetching once at get() must equal the per-batch
host update() path — bit-for-bit for integer-count metrics (Accuracy,
TopK), within 1e-6 relative for floating losses — across dtypes and
padded last batches. Metrics without a device statistic must fall back
to host update() transparently. The whole point is that update_device
performs NO blocking fetch; get() performs exactly one.
"""
import math

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import metric as M
from mxnet_tpu import profiler


def _class_batches(rng, n_batches, batch, classes, dtype="float32"):
    out = []
    for _ in range(n_batches):
        label = rng.randint(0, classes, size=(batch,)).astype("float32")
        pred = rng.rand(batch, classes).astype(dtype)
        out.append((mx.nd.array(label), mx.nd.array(pred, dtype=dtype)))
    return out


def _reg_batches(rng, n_batches, batch):
    out = []
    for _ in range(n_batches):
        label = rng.rand(batch).astype("float32")
        pred = rng.rand(batch, 1).astype("float32")
        out.append((mx.nd.array(label), mx.nd.array(pred)))
    return out


def _parity(make_metric, batches, exact):
    host = make_metric()
    dev = make_metric()
    for label, pred in batches:
        host.update([label], [pred])
    before = profiler.host_sync_stats()
    for label, pred in batches:
        dev.update_device([label], [pred])
    mid = profiler.host_sync_stats()
    # accumulation itself never blocks
    assert mid["blocking_fetches"] == before["blocking_fetches"]
    name_h, val_h = host.get()
    name_d, val_d = dev.get()
    after = profiler.host_sync_stats()
    # ... and the drain is exactly ONE fetch
    assert after["blocking_fetches"] == mid["blocking_fetches"] + 1
    assert after["metric_fetches"] == mid["metric_fetches"] + 1
    assert name_h == name_d
    if exact:
        assert val_h == val_d, (name_h, val_h, val_d)
    else:
        assert val_d == pytest.approx(val_h, rel=1e-6)
    return host, dev


@pytest.mark.parametrize("dtype", ["float32", "float16"])
def test_accuracy_parity_bit_for_bit(dtype):
    rng = np.random.RandomState(3)
    batches = _class_batches(rng, 5, 16, 7, dtype=dtype)
    _parity(lambda: M.create("acc"), batches, exact=True)


def test_accuracy_parity_id_shaped_preds():
    # pred already class-id shaped (no argmax reduction)
    rng = np.random.RandomState(4)
    batches = [
        (mx.nd.array(rng.randint(0, 5, (16,)).astype("float32")),
         mx.nd.array(rng.randint(0, 5, (16,)).astype("float32")))
        for _ in range(3)
    ]
    _parity(lambda: M.create("acc"), batches, exact=True)


def test_topk_parity():
    rng = np.random.RandomState(5)
    batches = _class_batches(rng, 4, 16, 9)
    _parity(lambda: M.create("top_k_accuracy", top_k=3), batches,
            exact=True)


def test_topk_parity_k_covers_all_classes():
    rng = np.random.RandomState(6)
    batches = _class_batches(rng, 2, 8, 3)
    _parity(lambda: M.create("top_k_accuracy", top_k=5), batches,
            exact=True)


def test_cross_entropy_parity():
    rng = np.random.RandomState(7)
    batches = _class_batches(rng, 5, 16, 6)
    _parity(lambda: M.create("ce"), batches, exact=False)


@pytest.mark.parametrize("name", ["mse", "rmse", "mae"])
def test_regression_parity(name):
    rng = np.random.RandomState(8)
    batches = _reg_batches(rng, 5, 16)
    _parity(lambda: M.create(name), batches, exact=False)


def test_loss_parity():
    rng = np.random.RandomState(9)
    batches = [
        (None, mx.nd.array(rng.rand(16, 4).astype("float32")))
        for _ in range(3)
    ]
    host, dev = M.create("loss"), M.create("loss")
    for _, pred in batches:
        host.update([], [pred])
        dev.update_device([], [pred])
    assert dev.get()[1] == pytest.approx(host.get()[1], rel=1e-6)


def test_composite_parity():
    rng = np.random.RandomState(10)
    batches = _class_batches(rng, 4, 16, 5)
    host = M.create(["acc", "ce"])
    dev = M.create(["acc", "ce"])
    for label, pred in batches:
        host.update([label], [pred])
        dev.update_device([label], [pred])
    names_h, vals_h = host.get()
    names_d, vals_d = dev.get()
    assert names_h == names_d
    assert vals_d[0] == vals_h[0]  # accuracy: exact
    assert vals_d[1] == pytest.approx(vals_h[1], rel=1e-6)


def test_unsupported_metric_falls_back_to_host():
    # CustomMetric overrides nothing device-side: update_device must
    # produce identical results via the host path
    def feval(label, pred):
        return float(np.abs(label - pred.ravel()).sum()), label.size

    rng = np.random.RandomState(11)
    batches = _reg_batches(rng, 3, 8)
    host = M.CustomMetric(feval, name="x")
    dev = M.CustomMetric(feval, name="x")
    assert not dev.supports_device()
    for label, pred in batches:
        host.update([label], [pred])
        dev.update_device([label], [pred])
    assert dev.get() == host.get()


def test_subclass_with_custom_update_keeps_host_path():
    # a user subclass overriding update() must NOT be routed through
    # the inherited device statistic (its update logic would be lost)
    calls = []

    class MyAcc(M.Accuracy):
        def update(self, labels, preds):
            calls.append(1)
            super().update(labels, preds)

    m = MyAcc()
    assert not m.supports_device()
    rng = np.random.RandomState(12)
    label, pred = _class_batches(rng, 1, 8, 4)[0]
    m.update_device([label], [pred])
    assert calls


def test_reset_drops_pending():
    rng = np.random.RandomState(13)
    label, pred = _class_batches(rng, 1, 8, 4)[0]
    m = M.create("acc")
    m.update_device([label], [pred])
    m.reset()
    assert math.isnan(m.get()[1])


def test_update_auto_routing(monkeypatch):
    rng = np.random.RandomState(14)
    label, pred = _class_batches(rng, 1, 8, 4)[0]

    m = M.create("acc")
    M.update_auto(m, [label], [pred])
    assert len(m._pending) == 1  # device path taken by default

    monkeypatch.setenv("MXNET_DEVICE_METRICS", "0")
    m2 = M.create("acc")
    M.update_auto(m2, [label], [pred])
    assert not m2._pending and m2.num_inst == 8  # host path


def test_score_parity_with_padded_last_batch(monkeypatch):
    """End to end through Module.score: 22 samples / batch 8 -> the
    last batch carries pad rows; device- and host-accumulated results
    must agree exactly for accuracy."""
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=16, name="fc1")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=4, name="fc2"),
        name="softmax")

    rng = np.random.RandomState(15)
    x = rng.rand(22, 10).astype(np.float32)
    y = rng.randint(0, 4, size=(22,)).astype(np.float32)

    def score_once():
        it = mx.io.NDArrayIter(x, y, batch_size=8, shuffle=False)
        mod = mx.mod.Module(net, context=[mx.cpu()])
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label, for_training=False)
        mx.random.seed(0)
        mod.init_params()
        return dict(mod.score(it, ["acc", "ce"]))

    dev_res = score_once()
    monkeypatch.setenv("MXNET_DEVICE_METRICS", "0")
    host_res = score_once()
    assert dev_res["accuracy"] == host_res["accuracy"]
    assert dev_res["cross-entropy"] == pytest.approx(
        host_res["cross-entropy"], rel=1e-6)


def test_mesh_sharded_preds_take_a_default_device_label():
    """Module(context=[...], kvstore="tpu").fit — the README's
    data-parallel path: the fused mesh step's output is sharded over
    the mesh while the iterator's label sits on the default device;
    the device metric must co-locate them, not raise "incompatible
    devices"."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    rng = np.random.RandomState(0)
    label = rng.randint(0, 3, size=(8,)).astype("float32")
    pred = rng.rand(8, 3).astype("float32")
    sharded = mx.nd.NDArray(jax.device_put(
        pred, NamedSharding(mesh, P("data"))))
    host, dev = M.Accuracy(), M.Accuracy()
    host.update([mx.nd.array(label)], [mx.nd.array(pred)])
    dev.update_device([mx.nd.array(label)], [sharded])
    assert dev.get() == host.get()
