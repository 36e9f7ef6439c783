"""Serve a saved checkpoint with dynamic batching.

The deploy story end-to-end: train-side `save_checkpoint` writes the
two-file artifact (`prefix-symbol.json` + `prefix-0001.params`); the
serving tier loads it into a `ModelServer`, which pre-traces a small
(batch, length) bucket grid at load time and then maps ragged traffic
onto those compiled programs — dynamic batching, padding, deadlines,
and backpressure all behind a `predict()`/`submit()` front door.

Gates: every served output must match a direct single-request
`Predictor.forward()` bit-for-bit modulo padding, and steady-state
serving must add ZERO compiled-program traces (the bucketing
contract, provable via `exec_cache.cache_stats`).
"""
import argparse
import os
import sys
import tempfile

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import mxnet_tpu as mx
from mxnet_tpu import serving


def build_net(vocab=1000, embed=16, classes=5):
    """Tiny text classifier: Embedding -> mean-pool -> FC."""
    data = mx.sym.Variable("data")
    net = mx.sym.Embedding(data, input_dim=vocab, output_dim=embed,
                           name="embed")
    net = mx.sym.mean(net, axis=1)
    return mx.sym.FullyConnected(net, num_hidden=classes, name="fc")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=60)
    ap.add_argument("--vocab", type=int, default=1000)
    args = ap.parse_args()

    net = build_net(vocab=args.vocab)
    shapes, _, _ = net.infer_shape(data=(1, 32))
    rs = np.random.RandomState(0)
    arg_params = {
        n: mx.nd.array(rs.normal(0, 0.1, s).astype("float32"))
        for n, s in zip(net.list_arguments(), shapes) if n != "data"
    }

    with tempfile.TemporaryDirectory() as tmp:
        prefix = os.path.join(tmp, "textclf")
        mx.model.save_checkpoint(prefix, 1, net, arg_params, {})

        # reference: the same checkpoint through a plain Predictor,
        # one request at a time, padded to the same length bucket the
        # server picks (identical math -> near-bitwise agreement).
        # Built & traced FIRST so the zero-retrace checks below see
        # only serving traffic (the refs bind float32 data — a
        # different cache signature than the int32 serving cells).
        buckets = (8, 16, 32)
        ref = mx.Predictor.from_checkpoint(prefix, 1, {"data": (1, 32)})
        ref_by_len = {L: ref.reshaped({"data": (1, L)})
                      for L in buckets}
        for L, r in ref_by_len.items():
            r.set_input("data", np.zeros((1, L), np.float32))
            r.forward()
            r.get_output()

        server = serving.ModelServer(max_batch=8, max_wait_us=2000)
        server.load_checkpoint(
            "textclf", prefix, 1,
            input_specs={"data": ("L",)},        # ragged token axis
            input_dtypes={"data": "int32"},
            length_buckets=buckets)              # grid pre-traced here

        base = mx.exec_cache.cache_stats()["traces"]
        lengths = rs.randint(1, 33, size=args.requests)
        futs, queries = [], []
        for n in lengths:
            ids = rs.randint(0, args.vocab, size=(int(n),))
            queries.append(ids)
            futs.append(server.submit(
                "textclf", {"data": ids.astype("int32")},
                deadline_ms=10_000))

        for ids, fut in zip(queries, futs):
            (scores,) = fut.result(timeout=30)
            L = serving.pick_bucket(len(ids), buckets)
            padded = np.zeros((1, L), np.float32)
            padded[0, : len(ids)] = ids
            r = ref_by_len[L]
            r.set_input("data", padded)
            r.forward()
            np.testing.assert_allclose(scores, r.get_output()[0],
                                       rtol=1e-5, atol=1e-6)

        snap = server.registry.get("textclf").stats.snapshot()
        traces_added = mx.exec_cache.cache_stats()["traces"] - base
        print(f"served {snap['completed']} requests in "
              f"{snap['batches']} batches | batch_fill "
              f"{snap['batch_fill']} | padding_waste "
              f"{snap['padding_waste']} | p50 {snap['p50_ms']} ms | "
              f"p99 {snap['p99_ms']} ms | new traces {traces_added}")
        assert snap["completed"] == args.requests
        assert traces_added == 0, "steady state must not retrace"
        assert snap["traces_since_warmup"] == 0
        server.stop()
    print("serving checkpoint demo OK")


if __name__ == "__main__":
    main()
