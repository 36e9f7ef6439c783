#!/usr/bin/env python
"""CI gate: generated-kernel codegen proves parity and never drops a
group silently; the merged ragged step keeps the decode contract.

Runtime checks over a net exercising all three codegen templates
(elementwise chain, scale+bias+activation, chain + absorbed full
reduction), bound with MXNET_FUSION_CODEGEN=0 and =1
(MXNET_FUSION_INTERPRET=1 so the generated-kernel path actually runs
on the CPU gate host):

  1. every __fusion_group__ the pass marks either lowers to a
     generated kernel WITH a build-time parity proof, or carries a
     counted fallback reason — groups_seen == lowered + fallback,
     zero parity failures, no group unaccounted,
  2. fused forward AND backward match the composed-lax fallback arm
     to 1e-6,
  3. fused and fallback programs take DIFFERENT exec-cache entries
     (the codegen decision is in the key),
  4. every lowered group has kind="kernel" + "kernel_lax" seconds in
     the CalibrationStore (the tuner's fuse-vs-fallback evidence),
  5. the merged-step engine (MXNET_DECODE_MERGED_STEP default) drops
     the per-length tail-prefill programs from the warmup grid and
     still decodes prefix-cache-hit traffic token-identically to the
     dense reference at zero steady-state retraces.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["MXNET_FUSION_INTERPRET"] = "1"
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import decoding as dec  # noqa: E402
from mxnet_tpu import exec_cache, passes  # noqa: E402

RTOL = 1e-6


def _net(hidden):
    x = mx.sym.Variable("x")
    g = mx.sym.Variable("g")
    b = mx.sym.Variable("b")
    h = mx.sym.FullyConnected(x, num_hidden=hidden, name="fc1")
    h = mx.sym.elemwise_mul(h, g)            # scale+bias+act group
    h = mx.sym.elemwise_add(h, b)
    h = mx.sym.Activation(h, act_type="tanh")
    h = mx.sym.FullyConnected(h, num_hidden=hidden, name="fc2")
    t = mx.sym.sigmoid(h)                    # elementwise chain ...
    t = mx.sym.square(t)
    return mx.sym.sum(t * 0.5)               # ... + absorbed reduce


def _arm(codegen, vals, shapes, hidden):
    os.environ["MXNET_FUSION_CODEGEN"] = codegen
    exec_cache.clear()
    passes.clear_memo()
    exe = _net(hidden).simple_bind(mx.cpu(), **shapes)
    exe.forward(is_train=True,
                **{n: mx.nd.array(v) for n, v in vals.items()})
    outs = [o.asnumpy() for o in exe.outputs]
    exe.backward()
    grads = {n: g.asnumpy() for n, g in exe.grad_dict.items()
             if g is not None}
    return outs, grads, exe


def check_codegen():
    hidden = 128
    shapes = {"x": (8, 64), "g": (8, hidden), "b": (8, hidden)}
    rs = np.random.RandomState(0)
    vals = {n: (rs.rand(*s) + 0.5).astype("float32")
            for n, s in shapes.items()}

    outs_lax, grads_lax, exe_off = _arm("0", vals, shapes, hidden)
    passes.reset_fusion_stats()
    outs_gen, grads_gen, exe_on = _arm("1", vals, shapes, hidden)

    fst = passes.fusion_stats()
    assert fst["groups_seen"] >= 2, fst
    assert fst["groups_seen"] == (fst["groups_lowered"]
                                  + fst["groups_fallback"]), \
        f"unaccounted fusion groups: {fst}"
    assert fst["parity_failures"] == 0, fst
    assert fst["groups_lowered"] >= 1, \
        f"nothing lowered on the interpret-forced gate host: {fst}"
    recs = passes.fusion_group_records()
    for digest, rec in recs.items():
        assert rec["decision"] == "pallas" or rec["reason"], \
            f"group {digest} fell back with no counted reason: {rec}"

    for a, b in zip(outs_lax, outs_gen):
        np.testing.assert_allclose(a, b, rtol=RTOL, atol=RTOL)
    for n in grads_lax:
        np.testing.assert_allclose(grads_lax[n], grads_gen[n],
                                   rtol=RTOL, atol=RTOL,
                                   err_msg=f"grad {n}")

    assert exe_on._cache_key != exe_off._cache_key, \
        "fused and fallback programs share an exec-cache entry"

    from mxnet_tpu.profiling import calibration_store
    store = calibration_store()
    lowered = [d for d, r in recs.items() if r["decision"] == "pallas"]
    for d in lowered:
        for kind in ("kernel", "kernel_lax"):
            sec = store.measured_seconds(d, "cpu", kind=kind)
            assert sec is not None and sec > 0, \
                f"no {kind} calibration record for group {d}"

    print(f"fusion-check (i-iv) OK: {fst['groups_seen']} groups, "
          f"{fst['groups_lowered']} lowered "
          f"({', '.join(sorted(fst['templates']))}), "
          f"{fst['groups_fallback']} fallback "
          f"{fst['fallback_reasons']}, parity "
          f"{fst['parity_checks']} checks / 0 failures, "
          f"{len(lowered)} groups calibrated")


def check_merged_step():
    cfg = dec.DecoderConfig(vocab=32, d_model=16, n_layers=2,
                            n_heads=2, d_ff=32, max_len=64)
    params = dec.init_decoder_params(cfg, seed=0)

    def model(merged):
        return dec.DecodedModel(
            "gate", 1, params, cfg, max_batch=2, page_size=4,
            num_pages=32, page_buckets=(1, 2, 4), max_tokens=8,
            prefix_cache=True, merged_step=merged)

    split = model(False)
    split_counts = split.engine.trace_counts()
    split.close()
    assert any(k.startswith("prefill_tail@") for k in split_counts)

    import jax.numpy as jnp

    def ref_greedy(prompt, n):
        toks, out = list(prompt), []
        for _ in range(n):
            lg = dec.reference_logits(
                params, np.asarray([toks], np.int32), cfg)
            nxt = int(jnp.argmax(lg[0, -1]))
            if nxt == cfg.eos_id:
                break
            out.append(nxt)
            toks.append(nxt)
        return out

    m = model(True)
    try:
        counts = m.engine.trace_counts()
        assert not any(k.startswith("prefill_tail@") for k in counts), \
            f"merged grid still has tail programs: {counts}"
        assert sum(counts.values()) < sum(split_counts.values())
        floor = m.engine.traces()
        shared = list(range(5, 13))              # two full pages
        prompts = [shared + [13], shared + [14, 15], [3, 4],
                   shared + [16, 17, 18], shared + [19]]
        for prompt in prompts:
            out = m.generate(prompt, max_new_tokens=6, timeout=60)
            ref = ref_greedy(prompt, 6)
            assert out == ref, (prompt, out, ref)
        assert m.engine.traces() == floor, "merged step retraced"
        snap = m.stats.snapshot()
        assert snap["traces_since_warmup"] == 0
        hit = snap["prefix_hit_rate"]
    finally:
        m.close()
    print(f"fusion-check (v) OK: warmup grid "
          f"{sum(split_counts.values())} -> {sum(counts.values())} "
          f"programs, {len(prompts)} ragged-tail requests "
          f"token-identical, 0 retraces, prefix hit rate {hit:.3f}")


def main():
    check_codegen()
    check_merged_step()


if __name__ == "__main__":
    main()
