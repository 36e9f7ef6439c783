#!/usr/bin/env python
"""Quantized-serving CI gate (make quant-check).

Two halves, mirroring the tentpole:

1. int8 KV pages, in-process — on the CI decoder (head_dim 16):
     * teacher-forced parity probe: greedy top-1 agreement >= 0.9
       vs float32, measured pool capacity ratio >= 1.9x, zero
       post-warmup retraces inside the probe;
     * real int8 DecodedModel traffic: zero steady-state retraces,
       zero quant clips (healthy numerics), int8 pool stats exposed;
     * dtype-salted prefix digests: an int8 chain never intersects a
       float32 chain for the same tokens.

2. weight-only int8 bundles, across real process boundaries
   (the check_coldstart.py recipe):
     * warm    — builds + warms an int8-KV decoded model, saves a
                 quantize="int8" bundle, prints its greedy stream;
     * restore — a FRESH interpreter mounts the bundle: zero traces,
                 zero XLA compiles, same kv_dtype, and a token stream
                 identical to the warm process's (drift tolerance:
                 exact, since restore dequantizes the same codes);
     * strip   — the parent deletes the manifest's quantization
                 record; the restore must be REFUSED (BundleError
                 naming the precision mismatch), never served.
"""
import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

AGREEMENT_FLOOR = 0.9     # declared greedy top-1 tolerance
CAPACITY_FLOOR = 1.9      # sequences-per-pool vs float32


def _ci_cfg():
    from mxnet_tpu import decoding as dec

    return dec.DecoderConfig(vocab=64, d_model=32, n_layers=2,
                             n_heads=2, d_ff=64, max_len=128)


def gate_parity_and_capacity():
    from mxnet_tpu import decoding as dec

    cfg = _ci_cfg()
    params = dec.init_decoder_params(cfg, seed=0)
    probe = dec.quant_parity_probe(params, cfg,
                                   prompt=[2, 9, 4, 17, 3],
                                   max_new=16, kv_dtype="int8")
    assert probe["top1_agreement"] >= AGREEMENT_FLOOR, probe
    assert probe["kv_pool_capacity_ratio"] >= CAPACITY_FLOOR, probe
    assert probe["retraces"] == 0, probe
    print(f"parity OK: agreement {probe['top1_agreement']}, "
          f"capacity {probe['kv_pool_capacity_ratio']}x, "
          f"drift {probe['logit_drift_max']}, 0 retraces")
    return probe


def gate_int8_traffic():
    import numpy as np

    from mxnet_tpu import decoding as dec

    cfg = _ci_cfg()
    params = dec.init_decoder_params(cfg, seed=0)
    m = dec.DecodedModel("ci-int8", 1, params, cfg, max_batch=4,
                         page_size=4, num_pages=64,
                         page_buckets=(1, 2, 4), max_tokens=12,
                         kv_dtype="int8", queue_cap=64)
    try:
        floor = m.engine.traces()
        rs = np.random.RandomState(0)
        futs = [m.submit([int(t) for t in
                          rs.randint(2, cfg.vocab, size=6)],
                         max_new_tokens=10) for _ in range(12)]
        for f in futs:
            assert f.result(240)
        assert m.engine.traces() == floor, "int8 steady-state retrace"
        snap = m.stats.snapshot()
        assert snap["traces_since_warmup"] == 0, snap
        assert snap["kv_dtype"] == "int8", snap
        assert snap["quant_clip_values"] == 0, snap
        print(f"traffic OK: {snap['decode_tokens']} tokens at int8, "
              f"0 retraces, 0 clips, "
              f"{snap['kv_bytes_per_token']} B/token")
    finally:
        m.close()


def gate_digest_salting():
    from mxnet_tpu.decoding.prefix import page_digests

    toks = list(range(1, 33))
    f32 = set(page_digests(toks, 4, "float32"))
    i8 = set(page_digests(toks, 4, "int8"))
    assert len(f32) == len(i8) == 8
    assert not (f32 & i8), "cross-dtype digest collision"
    print("digest salting OK: int8/float32 chains disjoint")


_COMMON = """
import json, os, sys
import numpy as np
from mxnet_tpu import decoding as dec, exec_cache, serving
from mxnet_tpu.profiling import device_stats

BUNDLE = os.environ["QUANT_BUNDLE"]
CFG = dec.DecoderConfig(vocab=64, d_model=32, n_layers=2, n_heads=2,
                        d_ff=64, max_len=128)
PROMPT = [2, 9, 4, 17, 3]

def report(extra):
    s = exec_cache.cache_stats()
    t = device_stats().get("totals", {})
    rec = {"traces": s["traces"], "compiles": t.get("compiles", 0)}
    rec.update(extra)
    print(json.dumps(rec))
"""

_WARM = _COMMON + """
params = dec.init_decoder_params(CFG, seed=0)
m = dec.DecodedModel("lm", 1, params, CFG, max_batch=2, page_size=4,
                     num_pages=32, page_buckets=(1, 2, 4),
                     max_tokens=12, kv_dtype="int8",
                     prefix_cache=False)
out = m.generate(PROMPT, max_new_tokens=8, timeout=120)
serving.save_bundle(m, BUNDLE, quantize="int8")
m.close(drain=False)
report({"out": out})
"""

_RESTORE = _COMMON + """
reg = serving.ModelRegistry()
m = reg.load_bundle(BUNDLE)
out = m.generate(PROMPT, max_new_tokens=8, timeout=120)
m.close(drain=False)
report({"out": out, "kv_dtype": m.engine.kv_dtype})
"""

_STRIPPED = _COMMON + """
from mxnet_tpu.serving import BundleError
try:
    serving.ModelRegistry().load_bundle(BUNDLE)
except BundleError as e:
    assert "precision" in str(e), e
    report({"refused": True})
else:
    report({"refused": False})
"""


def _run_child(code, bundle, cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", QUANT_BUNDLE=bundle,
               MXNET_EXEC_CACHE_DIR=cache_dir)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def gate_quantized_bundle():
    with tempfile.TemporaryDirectory() as td:
        bundle = os.path.join(td, "lm8.bundle")
        warm = _run_child(_WARM, bundle, os.path.join(td, "warmc"))
        # the warm process pays the compile grid (decode-tier traces
        # are engine-internal, not exec_cache binds — compiles are
        # the cross-tier evidence)
        assert warm["compiles"] > 0, warm
        restore = _run_child(_RESTORE, bundle,
                             os.path.join(td, "restc"))
        assert restore["traces"] == 0, restore
        assert restore["compiles"] == 0, restore
        assert restore["kv_dtype"] == "int8", restore
        assert restore["out"] == warm["out"], (warm, restore)
        print(f"bundle OK: quantized restore at 0 traces/0 compiles, "
              f"stream identical ({len(warm['out'])} tokens)")

        # the strip: manifest says full precision, arrays are int8
        mpath = os.path.join(bundle, "manifest.json")
        with open(mpath) as f:
            manifest = json.load(f)
        del manifest["quantization"]
        with open(mpath, "w") as f:
            json.dump(manifest, f)
        stripped = _run_child(_STRIPPED, bundle,
                              os.path.join(td, "stripc"))
        assert stripped["refused"], stripped
        print("refusal OK: stripped quantization record rejected")


def main():
    gate_digest_salting()
    gate_parity_and_capacity()
    gate_int8_traffic()
    gate_quantized_bundle()
    print("quant gates passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
