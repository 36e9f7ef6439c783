"""Generated Pallas kernels (mxnet_tpu.passes.pallas_codegen): every
template's interpret-mode parity (forward AND backward) through the
fused executor path against the composed-lax fallback, structural
fallbacks counted with reasons (never silently dropped), exec-cache
key separation between fused and fallback programs, kind="kernel"
calibration records, the ragged paged-attention kernel against a
dense numpy oracle for MIXED prefill+decode batches, and the
merged-step warmup trace-grid shrink with zero steady-state
retraces."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import decoding as dec
from mxnet_tpu import exec_cache, passes
from mxnet_tpu.decoding import attention as attn
from mxnet_tpu.decoding.blocks import PageError
from mxnet_tpu.passes import pallas_codegen as pc
from mxnet_tpu.passes.ir import Graph

jnp = pytest.importorskip("jax.numpy")


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """Default knobs, empty caches, zeroed codegen state per test."""
    for var in ("MXNET_GRAPH_PASSES", "MXNET_FUSION_CODEGEN",
                "MXNET_FUSION_MIN_GROUP", "MXNET_FUSION_INTERPRET",
                "MXNET_DECODE_KERNEL", "MXNET_DECODE_MERGED_STEP",
                "MXNET_DECODE_PREFIX_CACHE"):
        monkeypatch.delenv(var, raising=False)
    exec_cache.clear()
    exec_cache.reset_stats()
    passes.clear_memo()
    passes.reset_pass_stats()
    passes.reset_fusion_stats()
    dec.stats._registry.clear()
    yield
    exec_cache.clear()
    exec_cache.reset_stats()
    passes.clear_memo()
    passes.reset_pass_stats()
    passes.reset_fusion_stats()


# ------------------------------------------------------- template nets
def _elemwise_net():
    x = mx.sym.Variable("x")
    h = mx.sym.sigmoid(x)
    h = mx.sym.square(h)
    return h * 0.5


def _scale_bias_act_net():
    x = mx.sym.Variable("x")
    g = mx.sym.Variable("g")
    b = mx.sym.Variable("b")
    h = mx.sym.elemwise_mul(x, g)
    h = mx.sym.elemwise_add(h, b)
    return mx.sym.Activation(h, act_type="tanh")


def _reduction_net():
    x = mx.sym.Variable("x")
    y = mx.sym.Variable("y")
    return mx.sym.sum(mx.sym.relu(x) * y)


def _run(sym, vals, shapes, codegen):
    """Bind + forward + backward under one codegen setting; returns
    (outputs, grads, the bound executor)."""
    os.environ["MXNET_FUSION_CODEGEN"] = codegen
    os.environ["MXNET_FUSION_INTERPRET"] = "1"
    exec_cache.clear()
    passes.clear_memo()
    exe = sym.simple_bind(mx.cpu(), **shapes)
    exe.forward(is_train=True,
                **{n: mx.nd.array(v) for n, v in vals.items()})
    outs = [o.asnumpy() for o in exe.outputs]
    exe.backward()
    grads = {n: g.asnumpy() for n, g in exe.grad_dict.items()
             if g is not None}
    return outs, grads, exe


def _fusion_parity(sym, template, **shapes):
    """Fused executor (generated kernels, interpret mode) must match
    the composed-lax fallback to 1e-6 forward and backward, and the
    group must actually have lowered with the expected template."""
    rs = np.random.RandomState(0)
    vals = {n: (rs.rand(*s) + 0.5).astype("float32")
            for n, s in shapes.items()}
    outs_lax, grads_lax, _ = _run(sym, vals, shapes, "0")
    passes.reset_fusion_stats()
    outs_gen, grads_gen, exe = _run(sym, vals, shapes, "1")

    fst = passes.fusion_stats()
    assert fst["groups_lowered"] >= 1, fst
    assert fst["parity_failures"] == 0
    assert template in fst["templates"], fst
    assert exe._codegen_plan.fused, "no fused callable reached the plan"

    assert len(outs_lax) == len(outs_gen)
    for a, b in zip(outs_lax, outs_gen):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    assert set(grads_lax) == set(grads_gen)
    for n in grads_lax:
        np.testing.assert_allclose(grads_lax[n], grads_gen[n],
                                   rtol=1e-6, atol=1e-6,
                                   err_msg=f"grad {n}")


def test_elementwise_template_parity_fwd_bwd():
    _fusion_parity(_elemwise_net(), "elementwise", x=(8, 128))


def test_scale_bias_act_template_parity_fwd_bwd():
    _fusion_parity(_scale_bias_act_net(), "scale_bias_act",
                   x=(8, 128), g=(8, 128), b=(8, 128))


def test_reduction_template_parity_fwd_bwd():
    _fusion_parity(_reduction_net(), "reduction", x=(8, 128),
                   y=(8, 128))


def test_irregular_shapes_still_match_in_interpret_mode():
    # interpret mode runs whole-array blocks, so non-(8,128)-tiled
    # shapes lower too (on TPU they would fall back: irregular_shapes)
    _fusion_parity(_elemwise_net(), "elementwise", x=(5, 7))


# ------------------------------------------------ fallback accounting
def test_unsupported_op_group_falls_back_with_reason():
    """A group containing a non-elementwise op is stamped (and later
    counted) as fallback:unsupported_op:<name> — never lowered, never
    silently dropped."""
    x = mx.sym.Variable("x")
    fc = mx.sym.FullyConnected(x, num_hidden=8, name="fc")
    act = mx.sym.Activation(fc, act_type="relu")
    g = Graph.from_symbol(act)
    for gn in g.nodes:
        if not gn.is_variable:
            gn.extra["__fusion_group__"] = "fg_bad"
    pc.pallas_codegen(g)
    stamps = {gn.extra.get("__fusion_codegen__")
              for gn in g.nodes if not gn.is_variable}
    stamps.discard(None)          # only the group's out node is stamped
    assert stamps == {"fallback:unsupported_op:FullyConnected"}


def test_min_group_threshold_counts_too_small(monkeypatch):
    monkeypatch.setenv("MXNET_FUSION_MIN_GROUP", "5")
    monkeypatch.setenv("MXNET_FUSION_INTERPRET", "1")
    _elemwise_net().simple_bind(mx.cpu(), x=(4, 8))
    fst = passes.fusion_stats()
    assert fst["groups_seen"] == 1 and fst["groups_lowered"] == 0
    assert fst["fallback_reasons"] == {"too_small": 1}


def test_platform_fallback_counted_not_silent():
    """Without the interpret force flag there is no TPU here, so the
    group must take the counted lax fallback — and the books must
    balance: every group seen is lowered or has a reason."""
    os.environ["MXNET_FUSION_CODEGEN"] = "1"
    _elemwise_net().simple_bind(mx.cpu(), x=(8, 128))
    fst = passes.fusion_stats()
    assert fst["groups_seen"] == 1
    assert fst["groups_seen"] == (fst["groups_lowered"]
                                  + fst["groups_fallback"])
    assert fst["fallback_reasons"].get("platform") == 1
    recs = passes.fusion_group_records()
    assert all(r["decision"] in ("pallas", "fallback")
               and (r["decision"] == "pallas" or r["reason"])
               for r in recs.values())


def test_disabled_overrides_memoized_candidate_stamp(monkeypatch):
    """Flipping MXNET_FUSION_CODEGEN off after a fused bind must take
    effect even though optimize_for_bind memoized the stamped graph."""
    monkeypatch.setenv("MXNET_FUSION_INTERPRET", "1")
    sym = _elemwise_net()
    os.environ["MXNET_FUSION_CODEGEN"] = "1"
    exe_on = sym.simple_bind(mx.cpu(), x=(4, 8))
    os.environ["MXNET_FUSION_CODEGEN"] = "0"
    exe_off = sym.simple_bind(mx.cpu(), x=(4, 8))
    comp_off = exe_off._codegen_plan.cache_component
    assert any("fallback:disabled" in str(t) for t in comp_off)
    assert exe_on._cache_key != exe_off._cache_key


# -------------------------------------------------- exec-cache keying
def test_exec_cache_keys_separate_fused_from_fallback(monkeypatch):
    """Fused and fallback programs of the SAME graph never collide in
    the exec cache: the codegen decision is part of the key."""
    monkeypatch.setenv("MXNET_FUSION_INTERPRET", "1")
    sym = _elemwise_net()
    os.environ["MXNET_FUSION_CODEGEN"] = "1"
    exe_on = sym.simple_bind(mx.cpu(), x=(8, 128))
    os.environ["MXNET_FUSION_CODEGEN"] = "0"
    exe_off = sym.simple_bind(mx.cpu(), x=(8, 128))
    assert exe_on._cache_key != exe_off._cache_key
    assert any("pallas:" in str(t)
               for t in exe_on._codegen_plan.cache_component)
    # same setting twice IS a pure cache hit
    os.environ["MXNET_FUSION_CODEGEN"] = "1"
    exe_on2 = sym.simple_bind(mx.cpu(), x=(8, 128))
    assert exe_on2._cache_key == exe_on._cache_key


# ---------------------------------------------------- calibration
def test_kernel_timings_flow_into_calibration_store(monkeypatch):
    monkeypatch.setenv("MXNET_FUSION_INTERPRET", "1")
    os.environ["MXNET_FUSION_CODEGEN"] = "1"
    _elemwise_net().simple_bind(mx.cpu(), x=(8, 128))
    from mxnet_tpu.profiling import calibration_store

    store = calibration_store()
    digests = [d for d, r in passes.fusion_group_records().items()
               if r["decision"] == "pallas"]
    assert digests
    for d in digests:
        k = store.measured_seconds(d, "cpu", kind="kernel")
        lx = store.measured_seconds(d, "cpu", kind="kernel_lax")
        assert k is not None and k > 0
        assert lx is not None and lx > 0


def test_tuner_prefers_measured_lax_when_clearly_faster():
    from mxnet_tpu.passes.tuner import choose_fusion_kernel
    from mxnet_tpu.profiling import calibration_store

    store = calibration_store()
    store.record("fgtest0000000001", "cpu", "kernel", 10e-3)
    store.record("fgtest0000000001", "cpu", "kernel_lax", 1e-3)
    assert choose_fusion_kernel("fgtest0000000001", "cpu") == "lax"
    store.record("fgtest0000000002", "cpu", "kernel", 1e-3)
    store.record("fgtest0000000002", "cpu", "kernel_lax", 10e-3)
    assert choose_fusion_kernel("fgtest0000000002", "cpu") == "pallas"
    # no data -> the kernel (the measured default)
    assert choose_fusion_kernel("fgnodata00000000", "cpu") == "pallas"


# ------------------------------------------------- ragged attention
def test_ragged_kernel_mixed_prefill_decode_matches_dense():
    """ONE fixed-shape ragged call serving decode rows (full context)
    and tail-prefill rows (mid-prompt positions) must match a dense
    numpy softmax oracle row by row."""
    rs = np.random.RandomState(7)
    b, h, d, p, bp, n = 4, 2, 8, 4, 3, 16
    q = rs.randn(b, h, d).astype(np.float32)
    k_pages = rs.randn(n, p, h, d).astype(np.float32)
    v_pages = rs.randn(n, p, h, d).astype(np.float32)
    table = np.stack([rs.choice(np.arange(1, n), size=bp,
                                replace=False) for _ in range(b)]
                     ).astype(np.int32)
    # rows 0-1: decode rows attending their whole context; rows 2-3:
    # prompt-tail rows mid-prefill, attending only positions < their
    # own (intra-chunk causality via the per-row length)
    lengths = np.asarray([9, 12, 3, 6], np.int32)

    scale = 1.0 / np.sqrt(d)

    def oracle(row):
        ctx_k = k_pages[table[row]].reshape(bp * p, h, d)
        ctx_v = v_pages[table[row]].reshape(bp * p, h, d)
        ln = lengths[row]
        s = np.einsum("hd,thd->ht", q[row], ctx_k[:ln]) * scale
        e = np.exp(s - s.max(axis=-1, keepdims=True))
        w = e / e.sum(axis=-1, keepdims=True)
        return np.einsum("ht,thd->hd", w, ctx_v[:ln])

    for name in ("lax", "pallas"):
        out = np.asarray(attn.get_ragged_kernel(name)(
            q, k_pages.reshape(n, p, h * d),
            v_pages.reshape(n, p, h * d), table, lengths))
        for row in range(b):
            np.testing.assert_allclose(out[row], oracle(row),
                                       atol=1e-5,
                                       err_msg=f"{name} row {row}")


# ------------------------------------------------- merged decode step
CFG = dec.DecoderConfig(vocab=32, d_model=16, n_layers=2, n_heads=2,
                        d_ff=32, max_len=64)
PARAMS = dec.init_decoder_params(CFG, seed=0)


def _model(**kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 32)
    kw.setdefault("page_buckets", (1, 2, 4))
    kw.setdefault("max_tokens", 8)
    return dec.DecodedModel("lm", 1, PARAMS, CFG, **kw)


def _ref_greedy(prompt, n):
    toks, out = list(prompt), []
    for _ in range(n):
        lg = dec.reference_logits(PARAMS,
                                  np.asarray([toks], np.int32), CFG)
        nxt = int(jnp.argmax(lg[0, -1]))
        if nxt == CFG.eos_id:
            break
        out.append(nxt)
        toks.append(nxt)
    return out


def test_merged_step_shrinks_warmup_grid_and_keeps_parity():
    """The merged engine drops every per-length-bucket tail-prefill
    program from the warmup grid, and prefix-cache-hit traffic
    (which exercises the ragged tail rows) stays token-identical to
    the dense reference at zero steady-state retraces."""
    split = _model(prefix_cache=True, merged_step=False)
    split_counts = split.engine.trace_counts()
    split.close()
    assert any(k.startswith("prefill_tail@") for k in split_counts)

    m = _model(prefix_cache=True, merged_step=True)
    try:
        counts = m.engine.trace_counts()
        assert not any(k.startswith("prefill_tail@") for k in counts)
        assert sum(counts.values()) < sum(split_counts.values())

        floor = m.engine.traces()
        shared = [5, 6, 7, 8, 9, 10, 11, 12]   # two full pages
        prompts = [shared + [13], shared + [14, 15], [3, 4],
                   shared + [16, 17, 18]]
        for prompt in prompts:
            out = m.generate(prompt, max_new_tokens=6, timeout=60)
            assert out == _ref_greedy(prompt, 6), prompt
        assert m.engine.traces() == floor
        assert m.stats.snapshot()["traces_since_warmup"] == 0
    finally:
        m.close()


def test_merged_engine_rejects_dedicated_tail_prefill():
    m = _model(prefix_cache=True, merged_step=True)
    try:
        table = m.engine.allocator.alloc(2)
        with pytest.raises(PageError):
            m.engine.prefill(list(range(2, 8)), table, start=4)
        m.engine.allocator.free(table)
    finally:
        m.close()


def test_merged_step_off_without_prefix_cache():
    """No prefix cache -> no tail to merge: the engine stays on the
    split grid (speculative engines likewise keep their own step)."""
    m = _model(prefix_cache=False, merged_step=True)
    try:
        assert not m.engine.merged_step_enabled
        assert m.engine.step_rows == m.engine.max_batch
    finally:
        m.close()
