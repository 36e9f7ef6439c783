"""Quantized serving: the int8 KV page pool (per-page scale planes,
COW/prefix/speculative integration, zero-retrace discipline) and
weight-only int8 bundles.

The load-bearing invariants, each pinned here:

  * quantize/dequantize round-trips within scale/2 of a pure-numpy
    oracle, is idempotent, and counts clipped values only when the
    input holds NaN/Inf (the dequant-overflow watermark);
  * COW forks carry the scale plane with the page — a preempt/churn
    soak at int8 is BIT-identical to an uninterrupted int8 run;
  * speculative self-draft at int8 equals plain int8 greedy EXACTLY
    (accept rule degenerates to argmax agreement on shared pools);
  * prefix-page digests are dtype-seeded: an int8 advertisement can
    never cover a float32 prompt chain (fleet affinity safety);
  * a quantized bundle restores bit-identically to a model built
    from the dequantized params, and a precision mismatch between
    manifest and stored arrays is refused.
"""
import json
import os

import numpy as np
import pytest

from mxnet_tpu import decoding as dec
from mxnet_tpu import serving
from mxnet_tpu.decoding import quant as kvq
from mxnet_tpu.decoding.blocks import PageError
from mxnet_tpu.decoding.engine import quant_parity_probe
from mxnet_tpu.decoding.prefix import page_digests
from mxnet_tpu.fleet.affinity import AffinityIndex
from mxnet_tpu.serving import quant as wq
from mxnet_tpu.utils.persist import atomic_write_json, read_json

jnp = pytest.importorskip("jax.numpy")


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    for var in ("MXNET_DECODE_PAGE_SIZE", "MXNET_DECODE_PAGES",
                "MXNET_DECODE_MAX_BATCH", "MXNET_DECODE_PAGE_BUCKETS",
                "MXNET_DECODE_KERNEL", "MXNET_DECODE_RING_PREFILL",
                "MXNET_DECODE_MAX_TOKENS", "MXNET_DECODE_QUEUE_CAP",
                "MXNET_DECODE_PREFIX_CACHE", "MXNET_DECODE_SPEC_K",
                "MXNET_DECODE_SPEC_DRAFT", "MXNET_DECODE_KV_DTYPE",
                "MXNET_BUNDLE_QUANTIZE",
                "MXNET_BUNDLE_QUANTIZE_OVERRIDE"):
        monkeypatch.delenv(var, raising=False)
    dec.stats._registry.clear()
    yield


CFG = dec.DecoderConfig(vocab=32, d_model=16, n_layers=2, n_heads=2,
                        d_ff=32, max_len=64)
PARAMS = dec.init_decoder_params(CFG, seed=0)


def _model(**kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 32)
    kw.setdefault("page_buckets", (1, 2, 4))
    kw.setdefault("max_tokens", 8)
    kw.setdefault("kv_dtype", "int8")
    return dec.DecodedModel("lm8", 1, PARAMS, CFG, **kw)


# --------------------------------------------------- quantization core
def test_kv_roundtrip_oracle_vs_numpy():
    """decoding.quant vs a from-scratch numpy oracle: same int8 codes,
    same scales, dequant error bounded by scale/2, zero clips on
    finite input, idempotent on already-quantized values."""
    rng = np.random.default_rng(7)
    v = (rng.standard_normal((3, 5, 2, 8)) *
         rng.uniform(0.01, 100, (3, 5, 2, 1))).astype(np.float32)
    q, s, clips = kvq.quantize_values(v)
    q, s = np.asarray(q), np.asarray(s)
    # the oracle, written independently of the implementation
    amax = np.abs(v).max(axis=-1)
    scale_ref = np.maximum(amax, 1e-8) / 127.0
    q_ref = np.clip(np.rint(v / scale_ref[..., None]),
                    -127, 127).astype(np.int8)
    np.testing.assert_allclose(s, scale_ref, rtol=1e-6)
    np.testing.assert_array_equal(q, q_ref)
    assert int(clips) == 0

    d = np.asarray(kvq.dequantize_values(q, s))
    assert (np.abs(d - v) <= s[..., None] / 2 + 1e-7).all()
    # idempotence: requantizing the dequantized values reproduces the
    # exact codes (what makes shared pages bit-stable across rescans)
    q2, s2, _ = kvq.quantize_values(d)
    np.testing.assert_array_equal(np.asarray(q2), q)
    np.testing.assert_allclose(np.asarray(s2), s, rtol=1e-6)


def test_kv_clip_counter_fires_only_on_bad_numerics():
    v = np.ones((1, 4, 1, 8), np.float32)
    _, _, clips = kvq.quantize_values(v)
    assert int(clips) == 0
    v[0, 1, 0, 3] = np.nan
    v[0, 2, 0, 5] = np.inf
    _, _, clips = kvq.quantize_values(v)
    # a nonfinite value poisons its whole (slot, head) row: amax is
    # nonfinite, the scale falls back to the floor, and every value
    # in the row registers as clipped — 2 bad rows x head_dim 8
    assert int(clips) == 16


def test_canonical_pool_and_capacity():
    assert kvq.canonical(None) == "float32"
    assert kvq.canonical("bfloat16") == "bf16"
    with pytest.raises(PageError):
        kvq.canonical("int4")
    with pytest.raises(PageError, match="reserved"):
        kvq.canonical("fp8")   # in the enum, behind the same interface

    pool = kvq.make_pool((2, 6, 4, 2, 8), "int8")
    assert pool.data.dtype == jnp.int8
    # heads folded into the minor dimension; one scale per (slot, head)
    assert pool.data.shape == (2, 6, 4, 16)
    assert pool.scale.shape == (2, 6, 4 * 2)
    assert kvq.as_pool(pool) is pool
    layer = pool.layer(1)
    assert layer.pool is pool and layer.index == 1
    assert layer.shape == (6, 4, 16) and kvq.as_layer(layer) is layer
    f = kvq.make_pool((2, 6, 4, 2, 8), "float32")
    assert f.scale is None and f.kv_dtype == "float32"
    # int8 pools really are ~capacity_ratio smaller per token
    ratio = kvq.kv_bytes_per_token(f) / kvq.kv_bytes_per_token(pool)
    assert ratio == pytest.approx(kvq.capacity_ratio(8))
    assert kvq.capacity_ratio(8) == pytest.approx(32 / 12)
    assert kvq.check_capacity(8) and kvq.check_capacity(16)


# ------------------------------------------------- engine-level parity
# engine-warmup tests are slow-marked (each pays a full trace grid);
# ci/check_quant.sh runs them unfiltered in the quant-gate
@pytest.mark.slow
def test_int8_greedy_parity_capacity_and_zero_retrace():
    """The acceptance criteria at unit scale: teacher-forced greedy
    top-1 agreement within tolerance, pool capacity >= 1.9x, zero
    steady-state retraces at int8."""
    res = quant_parity_probe(PARAMS, CFG, prompt=[1, 2, 3, 4, 5],
                             max_new=12, kv_dtype="int8")
    assert res["top1_agreement"] >= 0.9
    assert res["kv_pool_capacity_ratio"] >= 1.9
    assert res["retraces"] == 0
    assert res["logit_drift_max"] < 0.5


def test_int8_model_grid_and_stats():
    """An int8 DecodedModel pre-traces the SAME program grid as
    float32 (dtype changes the digest, never the grid) and reports
    its precision through pool stats."""
    m = _model()
    try:
        assert m.engine.trace_counts() == {
            "copy_page": 1, "prefill@4": 1, "prefill@8": 1,
            "prefill@16": 1, "decode@1": 1, "decode@2": 1,
            "decode@4": 1}
        floor = m.engine.traces()
        out = m.generate([5, 6, 7], max_new_tokens=6, timeout=60)
        assert len(out) > 0
        assert m.engine.traces() == floor
        snap = m.stats.snapshot()
        assert snap["kv_dtype"] == "int8"
        assert snap["quant_clip_values"] == 0  # healthy numerics
        assert snap["pool_capacity_tokens"] == 31 * 4
        f32 = kvq.capacity_ratio(CFG.d_model // CFG.n_heads)
        assert snap["kv_bytes_per_token"] * f32 == pytest.approx(
            4 * 2 * CFG.d_model // CFG.n_heads * 2 * CFG.n_layers,
            rel=0.01)
    finally:
        m.close()


def test_cow_copy_page_carries_scale_plane():
    m = _model()
    try:
        eng = m.engine
        m.generate([5, 6, 7, 8], max_new_tokens=1, timeout=30)
        t1 = eng.allocator.alloc(1)
        src = t1[0]
        t2 = eng.allocator.fork(t1)
        page, copy_from = eng.allocator.make_writable(t2, 0)
        assert copy_from == src
        eng.copy_page(copy_from, page)
        ks, vs, ks_s, vs_s = eng.read_page_raw(0, src)
        kd, vd, kd_s, vd_s = eng.read_page_raw(0, page)
        np.testing.assert_array_equal(ks, kd)
        np.testing.assert_array_equal(vs, vd)
        assert ks_s is not None and vd_s is not None
        np.testing.assert_array_equal(ks_s, kd_s)
        np.testing.assert_array_equal(vs_s, vd_s)
        eng.allocator.free(t1)
        eng.allocator.free(t2)
    finally:
        m.close()


@pytest.mark.slow
def test_int8_churn_soak_bit_identical():
    """COW fork preserves scale planes under preemption churn: a pool
    far too small for the offered load (forced preempt/readmit over
    ~200 decode steps) must emit BIT-identical streams to an
    uninterrupted big-pool int8 run."""
    big = _model(max_batch=4, num_pages=64, max_tokens=12,
                 queue_cap=64)
    try:
        prompts = [[int(t) for t in
                    np.random.RandomState(i).randint(2, 32, size=6)]
                   for i in range(8)]
        want = [big.generate(p, max_new_tokens=10, timeout=120)
                for p in prompts]
    finally:
        big.close()
    small = _model(max_batch=4, num_pages=9, max_tokens=12,
                   queue_cap=64)
    try:
        for round_ in range(7):   # 56 requests through a 9-page pool
            futs = [small.submit(p, max_new_tokens=10,
                                 priority=(i + round_) % 2)
                    for i, p in enumerate(prompts)]
            got = [f.result(240) for f in futs]
            assert got == want
        snap = small.stats.snapshot()
        assert snap["preemptions"] > 0
        assert snap["steps"] >= 200   # a real soak, not a smoke test
        assert snap["quant_clip_values"] == 0
        small.engine.allocator.check()
    finally:
        small.close()


@pytest.mark.slow
def test_speculative_int8_exact_parity():
    """Self-draft speculative decoding at int8: draft and target
    share the same quantized pools, so greedy accept degenerates to
    argmax agreement — output EXACTLY equals plain int8 greedy."""
    plain = _model(prefix_cache=False)
    try:
        ref = {}
        for seed in range(4):
            p = [int(t) for t in
                 np.random.RandomState(seed).randint(2, 32, size=5)]
            ref[tuple(p)] = plain.generate(p, max_new_tokens=8,
                                           timeout=120)
    finally:
        plain.close()
    spec = _model(draft="self", spec_k=3, prefix_cache=False)
    try:
        for p, want in ref.items():
            assert spec.generate(list(p), max_new_tokens=8,
                                 timeout=120) == want
        snap = spec.stats.snapshot()
        assert snap["spec_proposed"] > 0
        assert snap["spec_accepted"] > 0
    finally:
        spec.close()


# ------------------------------------------------ digest dtype salting
def test_prefix_digests_dtype_salted():
    toks = list(range(1, 17))
    f32 = page_digests(toks, 4)
    assert f32 == page_digests(toks, 4, "float32")  # compat: same seed
    i8 = page_digests(toks, 4, "int8")
    assert len(i8) == len(f32) == 4
    assert set(i8).isdisjoint(f32)  # no boundary ever collides


def test_affinity_never_matches_across_dtypes():
    """A float32 router chain must not cover an int8 replica's
    advertisement (and vice versa) — affinity degrades to
    least-loaded instead of routing to untransferable pages."""
    toks = list(range(1, 17))
    idx_f = AffinityIndex(4, "float32")
    idx_q = AffinityIndex(4, "int8")
    idx_f.update("r-int8", page_digests(toks, 4, "int8"))
    idx_q.update("r-int8", page_digests(toks, 4, "int8"))
    idx_f.update("r-f32", page_digests(toks, 4, "float32"))
    assert idx_f.best(toks, ["r-int8"]) == (None, 0)   # cross: never
    assert idx_f.best(toks, ["r-f32", "r-int8"]) == ("r-f32", 4)
    assert idx_q.best(toks, ["r-int8"]) == ("r-int8", 4)


@pytest.mark.slow
def test_prefix_cache_advertises_dtype_seeded_chain():
    m = _model(prefix_cache=True)
    try:
        prompt = list(range(2, 12))
        m.generate(prompt, max_new_tokens=2, timeout=60)
        adv = m.scheduler.cache.cached_prefixes()
        assert adv, "prefix cache cached nothing"
        chain_q = page_digests(prompt, 4, "int8")
        chain_f = page_digests(prompt, 4, "float32")
        assert set(adv) & set(chain_q)
        assert not set(adv) & set(chain_f)
    finally:
        m.close()


# ------------------------------------------------- weight-only bundles
def test_weight_quantize_roundtrip_vs_numpy():
    rng = np.random.RandomState(11)
    params = {"w": (rng.randn(6, 16) * 3).astype(np.float32),
              "emb": rng.randn(32, 8).astype(np.float32),
              "ln": rng.randn(16).astype(np.float32),
              "steps": np.asarray(7, np.int64)}
    stored, rec = wq.quantize_params(params)
    assert rec["scheme"] == "int8"
    assert rec["quantized"] == ["emb", "w"]
    assert sorted(rec["skipped"]) == ["ln", "steps"]
    assert stored["w"].dtype == np.int8
    assert stored["w" + wq.SCALE_SUFFIX].shape == (16,)
    assert stored["ln"].dtype == np.float32  # vectors pass through
    back = wq.dequantize_params(stored, rec)
    assert sorted(back) == sorted(params)
    for name in rec["quantized"]:
        scale = stored[name + wq.SCALE_SUFFIX]
        assert (np.abs(back[name] - params[name])
                <= scale / 2 + 1e-7).all()
    np.testing.assert_array_equal(back["ln"], params["ln"])
    # a second quantize pass over restored params is a fixed point
    stored2, _ = wq.quantize_params(back)
    np.testing.assert_array_equal(stored2["w"], stored["w"])


@pytest.mark.slow
def test_quantized_bundle_roundtrip(tmp_path):
    """save_bundle(quantize="int8") → fresh registry restore equals a
    model built directly from the dequantized params (bit-exact), and
    the manifest records precision + kv_dtype."""
    m = _model(prefix_cache=False)
    out_dir = str(tmp_path / "lm8.bundle")
    try:
        serving.save_bundle(m, out_dir, quantize="int8")
    finally:
        m.close()
    manifest = serving.read_manifest(out_dir)
    assert manifest["quantization"]["scheme"] == "int8"
    assert manifest["kv_dtype"] == "int8"
    with np.load(os.path.join(out_dir, "params.npz")) as z:
        stored = {k: z[k] for k in z.files}
    assert stored["embed"].dtype == np.int8
    assert "embed" + wq.SCALE_SUFFIX in stored

    deq = wq.dequantize_params(stored, manifest["quantization"])
    ref = dec.DecodedModel("ref", 1, deq, CFG, max_batch=2,
                           page_size=4, num_pages=32,
                           page_buckets=(1, 2, 4), max_tokens=8,
                           kv_dtype="int8", prefix_cache=False)
    try:
        want = ref.generate([5, 6, 7], max_new_tokens=6, timeout=60)
    finally:
        ref.close()

    reg = serving.ModelRegistry()
    m2 = reg.load_bundle(out_dir)
    try:
        assert m2.engine.kv_dtype == "int8"
        assert m2.generate([5, 6, 7], max_new_tokens=6,
                           timeout=60) == want
    finally:
        m2.close()


def test_bundle_precision_mismatch_refused(tmp_path, monkeypatch):
    """Stripping the manifest's quantization record (or the scale
    planes) must refuse to load — a silent precision mismatch changes
    what the model computes — unless explicitly overridden."""
    m = _model(prefix_cache=False)
    out_dir = str(tmp_path / "lm8.bundle")
    try:
        serving.save_bundle(m, out_dir, quantize="int8")
    finally:
        m.close()
    mpath = os.path.join(out_dir, "manifest.json")
    manifest = read_json(mpath)
    del manifest["quantization"]          # the strip
    atomic_write_json(mpath, manifest)
    with pytest.raises(serving.BundleError, match="precision"):
        serving.ModelRegistry().load_bundle(out_dir)
    monkeypatch.setenv("MXNET_BUNDLE_QUANTIZE_OVERRIDE", "1")
    m2 = serving.ModelRegistry().load_bundle(out_dir)
    try:
        assert m2.generate([5, 6], max_new_tokens=2, timeout=60)
    finally:
        m2.close()


def test_save_bundle_env_default_and_bad_scheme(tmp_path, monkeypatch):
    m = _model(prefix_cache=False)
    try:
        with pytest.raises(serving.BundleError, match="quantization"):
            serving.save_bundle(m, str(tmp_path / "x.bundle"),
                                quantize="int4")
        monkeypatch.setenv("MXNET_BUNDLE_QUANTIZE", "int8")
        out = serving.save_bundle(m, str(tmp_path / "env.bundle"))
        assert serving.read_manifest(out)["quantization"][
            "scheme"] == "int8"
    finally:
        m.close()
