#!/usr/bin/env python
"""CI race-gate soak: serving + continuous decoding + multi-worker
DataLoader + telemetry exporter, all live at once, under the runtime
lock witness in raise mode.

This is the interleaving the static pass cannot synthesize: four
subsystems' worker threads contending for their locks in one process.
The witness records every thread's actual acquisition order
(attempt-time, lockdep-style), so

  - a genuine lock-order cycle anywhere raises LockOrderViolation in
    the culprit thread instead of deadlocking the soak,
  - the soak completing at all proves the combined workload is
    deadlock-free under the witnessed interleavings,
  - the dynamic held-before graph is joined back onto the static
    ConcurrencyModel (lock_sites) and every witnessed edge between
    statically-known locks is reported, flagging edges the
    interprocedural walk missed.

MXNET_LOCK_WITNESS=raise is exported before mxnet_tpu is imported, so
the factories are patched before any module-level lock exists and
every lock in the package is witnessed.
"""
import os
import sys
import threading
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["MXNET_LOCK_WITNESS"] = "raise"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import decoding as dec  # noqa: E402
from mxnet_tpu import serving, telemetry  # noqa: E402
from mxnet_tpu.analysis import concurrency, lockwitness  # noqa: E402
from mxnet_tpu.data import DataLoader  # noqa: E402

SOAK_TIMEOUT_S = 300


def _fail(msg):
    print(f"check_concurrency_soak: FAIL — {msg}")
    sys.exit(1)


def _params_for(net, **input_shapes):
    shapes, _, _ = net.infer_shape(**input_shapes)
    rs = np.random.RandomState(7)
    return {
        n: mx.nd.array(rs.uniform(-1, 1, s).astype("float32"))
        for n, s in zip(net.list_arguments(), shapes)
        if n not in input_shapes
    }


def drive_serving(errors):
    try:
        net = mx.sym.FullyConnected(
            mx.sym.Variable("data"), num_hidden=4, name="fc")
        server = serving.ModelServer(max_wait_us=1000, queue_cap=256)
        try:
            server.load("soak", net.tojson(),
                        _params_for(net, data=(1, 8)),
                        input_specs={"data": (8,)})
            rs = np.random.RandomState(0)
            futs = [server.submit(
                "soak", {"data": rs.rand(8).astype("float32")})
                for _ in range(48)]
            for f in futs:
                f.result(timeout=180)
        finally:
            server.stop()
    except Exception as e:  # noqa: BLE001 — collected by main
        errors.append(("serving", e))


def drive_decoding(errors):
    try:
        cfg = dec.DecoderConfig(vocab=32, d_model=16, n_layers=1,
                                n_heads=2, d_ff=32, max_len=64)
        params = dec.init_decoder_params(cfg, seed=0)
        model = dec.DecodedModel(
            "soakdec", 1, params, cfg, max_batch=2, page_size=4,
            num_pages=9, page_buckets=(1, 2, 4), queue_cap=64,
            max_tokens=8)
        try:
            rs = np.random.RandomState(3)
            futs = [model.submit(
                rs.randint(2, cfg.vocab, size=3).tolist(),
                max_new_tokens=6) for _ in range(6)]
            for f in futs:
                f.result(240)
        finally:
            model.close()
    except Exception as e:  # noqa: BLE001
        errors.append(("decoding", e))


def drive_data(errors):
    try:
        rs = np.random.RandomState(1)
        x = rs.rand(64, 4).astype("float32")
        y = rs.rand(64, 1).astype("float32")
        for _epoch in range(2):
            with DataLoader(x, 8, label=y, seed=5, num_workers=2,
                            queue_cap=2) as it:
                for _batch in it:
                    pass
    except Exception as e:  # noqa: BLE001
        errors.append(("data", e))


def drive_telemetry(errors, exporter):
    try:
        base = f"http://127.0.0.1:{exporter.port}"
        for _ in range(20):
            urllib.request.urlopen(base + "/metrics",
                                   timeout=10).read()
            urllib.request.urlopen(base + "/statusz",
                                   timeout=10).read()
    except Exception as e:  # noqa: BLE001
        errors.append(("telemetry", e))


def main():
    if not lockwitness.is_installed():
        _fail("witness not installed — MXNET_LOCK_WITNESS=raise "
              "should have armed it at package import")
    errors = []
    exporter = telemetry.start_exporter(port=0)
    try:
        threads = [
            threading.Thread(target=drive_serving, args=(errors,),
                             name="soak-serving", daemon=True),
            threading.Thread(target=drive_decoding, args=(errors,),
                             name="soak-decoding", daemon=True),
            threading.Thread(target=drive_data, args=(errors,),
                             name="soak-data", daemon=True),
            threading.Thread(target=drive_telemetry,
                             args=(errors, exporter),
                             name="soak-telemetry", daemon=True),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(SOAK_TIMEOUT_S)
        stuck = [t.name for t in threads if t.is_alive()]
        if stuck:
            _fail(f"soak deadlocked/stalled: {stuck} still alive "
                  f"after {SOAK_TIMEOUT_S}s")
    finally:
        exporter.stop()

    if errors:
        _fail("; ".join(f"{name}: {e!r}" for name, e in errors))
    cycles = lockwitness.violations()
    if cycles:
        _fail(f"witness recorded lock-order cycles: {cycles}")

    # ---- cross-check the dynamic graph against the static model
    files = []
    pkg = os.path.join(ROOT, "mxnet_tpu")
    import ast
    for dirpath, _dirs, fns in os.walk(pkg):
        if "__pycache__" in dirpath:
            continue
        for fn in fns:
            if fn.endswith(".py"):
                p = os.path.join(dirpath, fn)
                rel = os.path.relpath(p, ROOT).replace(os.sep, "/")
                with open(p, encoding="utf-8") as f:
                    try:
                        files.append((rel, ast.parse(f.read())))
                    except SyntaxError:
                        pass
    model = concurrency.ConcurrencyModel(files)
    matched, unmatched = lockwitness.cross_check(model, ROOT)
    dyn_edges = lockwitness.held_before_edges()
    static = model.static_edges()
    missed = [(a, b) for a, b in matched if (a, b) not in static]
    print(f"check_concurrency_soak: witnessed {len(dyn_edges)} "
          f"dynamic held-before edges; {len(matched)} between "
          f"statically-known locks ({len(static)} static edges); "
          f"{len(unmatched)} involve locks outside the static "
          "registry (stdlib/test internals)")
    for a, b in missed:
        print(f"  note: dynamic edge {a} -> {b} absent from the "
              "static graph (call-graph resolution miss — ordering "
              "still witnessed acyclic)")
    if not dyn_edges:
        _fail("soak witnessed no held-before edges at all — the "
              "witness is not observing the package's locks")
    print("check_concurrency_soak: OK — serving + decoding + data + "
          "telemetry ran concurrently under the witness with no "
          "lock-order cycle and no deadlock")
    return 0


if __name__ == "__main__":
    sys.exit(main())
