"""chip_smoke.py — the quickest proof that mxnet_tpu still starts on the chip.

Drives the two paths users enter through, once, in this one process, at
the full width of a model the repo supports (depth cut, weights random
from --seed), and checks what comes out by the repo's own means:

  train    ResNet-50 / 1000 classes / 224x224 / NHWC / space-to-depth
           stem / bf16 compute / batch 256 / SGD-momentum through
           `Module(..., context=mx.tpu()).fit(kvstore="tpu")` over an
           NDArrayIter, then the same steps with steps_per_dispatch=8
  serve    `ModelServer.load_decoder` + `submit_decode` with a
           DecoderConfig at d_model 2048 / 16 heads / d_ff 8192 /
           8 layers / vocab 32000 / max_len 2048: every stream
           token-identical to the unbatched greedy reference, with the
           tier's default paged attention (the in-place kernel on a
           TPU, the lax form elsewhere) and with the other one; int8
           pages' top-1 agreement
  kernels  every Pallas kernel on those paths, compiled (never
           interpreted on a TPU), against its lax reference
  --chips 4   (and then no other phase) the ResNet step data-parallel
           over four contexts against one chip, and one ShardingPlan
           data x tensor (2x2) transformer train step against the
           unsharded step

One JSON object per phase, then as the LAST line
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
with the device as jax reports it. Exit code 0 only with "ok": true.
Without a TPU the script fails at once and prints no result — unless
`--size tiny`, the CPU rehearsal: the same phases and checks at toy
widths (Pallas interpreted only because the backend is the CPU), whose
last line says "ok": false with the platform it found.

One process holds the chip: nothing here starts a child, and jax is not
imported before the arguments are read. The compile cache goes where
JAX_COMPILATION_CACHE_DIR says, else to <checkout>/.jax_cache
(mxnet_tpu.exec_cache_disk.place_jax_cache).
"""
import argparse
import gc
import json
import math
import random
import sys
import time
import traceback

SIZES = {
    "full": dict(
        resnet=dict(layers=50, image=224, classes=1000, batch=256),
        train_steps=8,
        decoder=dict(vocab=32000, d_model=2048, n_layers=8, n_heads=16,
                     d_ff=8192, max_len=2048),
        serve=dict(max_batch=8, page_size=16, num_pages=384,
                   page_buckets=(8, 32), requests=12, prompt=(4, 200),
                   new=(4, 24)),
        probe=dict(prompt=40, new=16, page_buckets=(8,)),
        flash=(4, 2048, 16, 128),
        transformer=dict(d_model=2048, num_heads=16, d_ff=8192,
                         num_layers=4, seq=2048, batch=8),
    ),
    "tiny": dict(
        resnet=dict(layers=18, image=64, classes=8, batch=8),
        train_steps=8,
        decoder=dict(vocab=64, d_model=32, n_layers=2, n_heads=2,
                     d_ff=64, max_len=128),
        serve=dict(max_batch=2, page_size=4, num_pages=64,
                   page_buckets=(4, 8), requests=5, prompt=(2, 12),
                   new=(2, 8)),
        probe=dict(prompt=6, new=8, page_buckets=(4,)),
        flash=(2, 64, 2, 16),
        transformer=dict(d_model=32, num_heads=4, d_ff=64, num_layers=2,
                         seq=16, batch=8),
    ),
}
STEPS_PER_DISPATCH = 8
# loss agreement across layouts, where only the order of sums differs:
# |a - b| <= RTOL * |a| + ATOL, bf16 compute in both: the ResNet's
# early steps amplify rounding, the transformer takes two small ones
DP_LOSS_RTOL, DP_LOSS_ATOL = 5e-2, 2e-2
PLAN_LOSS_RTOL, PLAN_LOSS_ATOL = 1e-2, 0.0
INT8_TOP1_FLOOR = 0.9   # what tests/test_quant.py demands of the probe


def _emit(record):
    print(json.dumps(record), flush=True)


class _JaxEvents:
    """Compile seconds and persistent-cache hits, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds

    def snapshot(self):
        return (self.compile_s, self.cache_hits, self.cache_misses)

    def since(self, snap):
        return {"compile_s": round(self.compile_s - snap[0], 2),
                "jax_cache_hits": self.cache_hits - snap[1],
                "jax_cache_misses": self.cache_misses - snap[2]}


def _run_phase(name, events, fn, *args):
    """Run one phase; print its JSON line; True when every check held."""
    snap, t0 = events.snapshot(), time.perf_counter()
    try:
        rec = fn(*args)
    except Exception as exc:  # a phase that raises is a failed phase
        traceback.print_exc()
        rec = {"checks": {}, "error": repr(exc)[:400]}
    gc.collect()
    checks = rec.get("checks", {})
    ok = bool(checks) and all(checks.values()) and "error" not in rec
    _emit({"phase": name, "ok": ok, **rec,
           "wall_s": round(time.perf_counter() - t0, 1),
           **events.since(snap)})
    return ok


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------ train
def _resnet_fit(cfg, seed, context, steps, steps_per_dispatch=1):
    """ResNet through Module.fit on one repeated synthetic batch.
    Returns (module, [loss seen by each batch_end_callback])."""
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models import get_resnet

    side, batch = cfg["image"], cfg["batch"]
    net = get_resnet(num_classes=cfg["classes"], num_layers=cfg["layers"],
                     image_shape=(3, side, side), layout="NHWC",
                     stem="space_to_depth")
    rs = np.random.RandomState(seed)
    x = rs.uniform(-1, 1, (batch, side, side, 3)).astype("float32")
    y = rs.randint(0, cfg["classes"], (batch,)).astype("float32")
    it = mx.io.NDArrayIter(np.tile(x, (steps, 1, 1, 1)), np.tile(y, steps),
                           batch_size=batch)
    mx.random.seed(seed)
    np.random.seed(seed)
    mod = mx.mod.Module(net, context=context)
    mod.cast_compute(jnp.bfloat16)
    losses = []

    def on_batch(param):
        losses.append(float(param.eval_metric.get()[1]))
        param.eval_metric.reset()

    mod.fit(it, eval_metric=mx.metric.CrossEntropy(), kvstore="tpu",
            optimizer="sgd",
            optimizer_params=(("learning_rate", 0.1), ("momentum", 0.9),
                              ("wd", 1e-4)),
            initializer=mx.initializer.Xavier(factor_type="in",
                                              magnitude=2.0),
            num_epoch=1, batch_end_callback=on_batch,
            steps_per_dispatch=steps_per_dispatch)
    return mod, losses


def _params_finite(mod):
    """fit's end state as fetched values: every parameter on the host."""
    import numpy as np

    args, auxs = mod.get_params()
    return all(bool(np.isfinite(v.asnumpy()).all())
               for v in list(args.values()) + list(auxs.values()))


def _fence_timing(mod, cfg, seed):
    """One more step, then time block_until_ready and the value fetch
    after it: if the first alone fences, the second costs nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx

    rs = np.random.RandomState(seed)
    side, batch = cfg["image"], cfg["batch"]
    b = mx.io.DataBatch(
        data=[mx.nd.array(rs.uniform(-1, 1, (batch, side, side, 3))
                          .astype("float32"))],
        label=[mx.nd.array(rs.randint(0, cfg["classes"], (batch,))
                           .astype("float32"))])
    mod.sync()   # drains, and warms the fetch's own tiny programs
    leaf = next(iter(mod._fused_step.params.values()))
    t0 = time.perf_counter()
    mod.forward_backward(b)
    mod.update()
    t1 = time.perf_counter()
    jax.block_until_ready(mod._fused_step.params)
    t2 = time.perf_counter()
    leaf = next(iter(mod._fused_step.params.values()))
    np.asarray(jax.device_get(jnp.ravel(leaf)[0]))
    t3 = time.perf_counter()
    return {"dispatch_s": round(t1 - t0, 4),
            "block_until_ready_s": round(t2 - t1, 4),
            "fetch_after_block_s": round(t3 - t2, 4)}


def phase_train(size, seed):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import exec_cache, profiling

    cfg, steps = size["resnet"], size["train_steps"]
    dev = mx.tpu().jax_device()
    mod, losses = _resnet_fit(cfg, seed, mx.tpu(), steps)
    fused = mod._fused_step
    checks = {
        "fused_step_built": fused is not None,
        "steps_ran": len(losses) == steps,
        "loss_finite": all(map(math.isfinite, losses)),
        "loss_fell": bool(losses) and losses[-1] < losses[0],
        "params_on_tpu": fused is not None and all(
            d.platform == "tpu" for a in fused.params.values()
            for d in a.devices()),
        "fit_ended_in_fetched_finite_params": _params_finite(mod),
    }
    fence = _fence_timing(mod, cfg, seed)
    del mod, fused
    gc.collect()

    # k steps per dispatch through the compiled lax.scan loop
    k = STEPS_PER_DISPATCH
    mod, k_losses = _resnet_fit(cfg, seed, mx.tpu(), steps,
                                steps_per_dispatch=k)
    checks.update({
        "k_loop_compiled": (k, True) in mod._fused_step._multi_cache,
        "k_loss_finite": bool(k_losses)
        and all(map(math.isfinite, k_losses)),
        "k_loss_fell": bool(k_losses) and bool(losses)
        and k_losses[-1] < losses[0],
        "k_fit_ended_in_fetched_finite_params": _params_finite(mod),
    })
    del mod
    cache = exec_cache.cache_stats()
    return {
        "checks": checks,
        "model": f"resnet{cfg['layers']}_b{cfg['batch']}_{cfg['image']}px"
                 "_nhwc_s2d_bf16",
        "steps": steps, "losses": [round(x, 4) for x in losses],
        "steps_per_dispatch": k,
        "k_losses": [round(x, 4) for x in k_losses],
        "peak_bytes_in_use": _peak_bytes(dev),
        "exec_cache": {n: cache[n] for n in ("hits", "misses", "traces")},
        "compiles": profiling.device_stats().get("totals", {})
        .get("compiles"),
        "fence": fence,
        "default_backend": jax.default_backend(),
    }


# ------------------------------------------------------------------ serve
def _requests(spec, vocab, seed):
    rng = random.Random(seed)
    return [([rng.randrange(2, vocab)
              for _ in range(rng.randint(*spec["prompt"]))],
             rng.randint(*spec["new"]))
            for _ in range(spec["requests"])]


def _greedy_reference(params, cfg, jobs, pad_to):
    """Unbatched greedy streams from the dense reference forward, every
    call padded to `pad_to` tokens so it compiles once, not per token
    (causal attention: the padding cannot reach position n-1)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu import decoding as dec

    @jax.jit
    def next_token(p, toks, n):
        logits = dec.reference_logits(p, toks, cfg)
        return jnp.argmax(logits[0, n - 1]).astype(jnp.int32)

    streams = []
    for prompt, n_new in jobs:
        toks = np.zeros((1, pad_to), np.int32)
        toks[0, :len(prompt)] = prompt
        n, out = len(prompt), []
        for _ in range(n_new):
            nxt = int(next_token(params, toks, np.int32(n)))
            if nxt == cfg.eos_id:
                break
            out.append(nxt)
            toks[0, n] = nxt
            n += 1
        streams.append(out)
    return streams


def phase_serve(size, seed):
    import jax

    # random weights give thin top-1 margins and the chip's default
    # float32 matmul is not exact: every arm of the phase runs at
    # "highest". Set process-wide, not with the (thread-local) context
    # manager: the scheduler thread dispatches the programs too.
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        return _serve(size, seed)
    finally:
        jax.config.update("jax_default_matmul_precision", was)


def _serve(size, seed):
    import jax.numpy as jnp

    from mxnet_tpu import decoding as dec
    from mxnet_tpu import serving
    from mxnet_tpu.decoding.engine import quant_parity_probe

    cfg = dec.DecoderConfig(**size["decoder"])
    spec, probe = size["serve"], size["probe"]
    max_context = spec["page_buckets"][-1] * spec["page_size"]
    jobs = _requests(spec, cfg.vocab, seed)
    assert all(len(p) + n <= max_context for p, n in jobs)
    checks, runs = {}, {}
    # one device copy of the weights, shared by the reference and
    # every engine (jnp.asarray of a device array is no copy)
    params = {k: jnp.asarray(v) for k, v in
              dec.init_decoder_params(cfg, seed=seed).items()}
    want = _greedy_reference(params, cfg, jobs, max_context)
    server = serving.ModelServer()
    # the tier's default and the form it did not pick
    other = "lax" if dec.config.kernel() == "pallas" else "pallas"
    try:
        for kernel in (None, other):
            name = f"lm-{kernel or 'default'}"
            model = server.load_decoder(
                name, params, cfg, kernel=kernel,
                max_batch=spec["max_batch"],
                page_size=spec["page_size"],
                num_pages=spec["num_pages"],
                page_buckets=spec["page_buckets"],
                max_tokens=spec["new"][1])
            floor = model.engine.traces()
            futs = [server.submit_decode(name, p, max_new_tokens=n)
                    for p, n in jobs]
            got = [f.result(600) for f in futs]
            same = sum(g == w for g, w in zip(got, want))
            text = model.engine.decode_program_text(
                spec["page_buckets"][0])
            runs[name] = {
                "kernel": model.engine.kernel_name,
                "streams_identical": f"{same}/{len(jobs)}",
                "tokens": sum(len(g) for g in got),
                "traces_since_warmup": model.engine.traces() - floor,
                "tpu_custom_call": "tpu_custom_call" in text,
            }
            checks[f"{name}_token_identical"] = same == len(jobs)
            checks[f"{name}_zero_retraces"] = (
                model.engine.traces() == floor
                and model.stats.snapshot()["traces_since_warmup"] == 0)
            server.unload(name)
            del model
            gc.collect()
        checks["pallas_run_has_tpu_custom_call"] = all(
            run["tpu_custom_call"] for run in runs.values()
            if run.get("kernel") == "pallas")
    finally:
        server.stop(drain=False)
    # int8 pages: teacher-forced top-1 agreement with float pages (the
    # pool sized like the server's, so the prefill programs are the
    # ones already compiled)
    rng = random.Random(seed + 1)
    prompt = [rng.randrange(2, cfg.vocab) for _ in range(probe["prompt"])]
    for kernel in (None, other):
        res = quant_parity_probe(
            params, cfg, prompt, max_new=probe["new"], kv_dtype="int8",
            page_size=spec["page_size"], num_pages=spec["num_pages"],
            page_buckets=probe["page_buckets"], kernel=kernel)
        name = f"int8-{kernel or 'default'}"
        runs[name] = {n: res[n] for n in (
            "top1_agreement", "positions_compared", "logit_drift_max",
            "kv_pool_capacity_ratio", "retraces")}
        checks[f"{name}_top1_agreement"] = \
            res["top1_agreement"] >= INT8_TOP1_FLOOR
        checks[f"{name}_zero_retraces"] = res["retraces"] == 0
        gc.collect()
    return {"checks": checks,
            "model": "decoder_d{d_model}_h{n_heads}_ff{d_ff}_L{n_layers}"
                     "_v{vocab}_len{max_len}".format(**size["decoder"]),
            "requests": len(jobs), "max_batch": spec["max_batch"],
            "matmul_precision": "highest", "runs": runs}


# ---------------------------------------------------------------- kernels
def _max_err(a, b):
    import numpy as np

    return float(np.max(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32))))


def _compiled_text(fn, *args):
    return fn.lower(*args).compile().as_text()


def _kernel_flash(shape, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.parallel.attention import (attention,
                                              attention_reference)

    rs = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(rs.standard_normal(shape), jnp.bfloat16)
               for _ in range(3))
    block = min(128, shape[1])
    flash = jax.jit(lambda q, k, v: attention(
        q, k, v, causal=True, impl="flash", block_q=block, block_k=block))
    ref = jax.jit(lambda q, k, v: attention_reference(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), causal=True))
    err = _max_err(flash(q, k, v), ref(q, k, v))
    return {"shape": list(shape), "dtype": "bfloat16",
            "max_err": round(err, 5), "ok": err < 5e-2,
            "tpu_custom_call":
                "tpu_custom_call" in _compiled_text(flash, q, k, v)}


def _kernel_paged(dcfg, spec, kv_dtype, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.decoding import attention as attn
    from mxnet_tpu.decoding import quant

    h, d = dcfg["n_heads"], dcfg["d_model"] // dcfg["n_heads"]
    b, p = 8, spec["page_size"]
    bp, n = spec["page_buckets"][-1], spec["num_pages"]
    rs = np.random.RandomState(seed)
    # two layers, the second one read: the kernels take the whole pool
    # and the layer's index (decoding/quant.py), never a slice of it
    pools = []
    for _ in range(2):
        pool = quant.make_pool((2, n, p, h, d), kv_dtype)
        vals = jnp.asarray(rs.standard_normal((n * p, h, d)), jnp.float32)
        pool, _ = quant.kv_scatter(
            pool, 1, jnp.repeat(jnp.arange(n), p),
            jnp.tile(jnp.arange(p), n), vals)
        pools.append(pool)
    q = jnp.asarray(rs.standard_normal((b, h, d)), jnp.float32)
    table = jnp.asarray(rs.randint(1, n, (b, bp)), jnp.int32)
    # ragged: an empty row (the kernel starts no copy for it and
    # returns zeros), one token, one page, the full bucket, the rest
    # anywhere; a row's pages past its length are whatever the table
    # drew, which no row may read
    lengths = rs.randint(1, bp * p + 1, (b,))
    lengths[:4] = 0, 1, p, bp * p
    live = lengths > 0
    lengths = jnp.asarray(lengths, jnp.int32)

    def on_layer(kernel):
        return jax.jit(lambda q, k, v, table, lengths: kernel(
            q, k.layer(1), v.layer(1), table, lengths))

    pallas = on_layer(attn.paged_attention_pallas)
    lax = on_layer(attn.paged_attention_lax)
    args = (q, pools[0], pools[1], table, lengths)
    with jax.default_matmul_precision("highest"):  # the lax twin's dots
        got = np.asarray(pallas(*args))
        err = _max_err(got[live], np.asarray(lax(*args))[live])
    return {"shape": [b, h, d], "page": [p, bp], "kv_dtype": kv_dtype,
            "lengths": lengths.tolist(), "max_err": round(err, 7),
            "ok": err < 1e-4 and not got[~live].any(),
            "tpu_custom_call":
                "tpu_custom_call" in _compiled_text(pallas, *args)}


def _kernel_rtc():
    import numpy as np

    import mxnet_tpu as mx

    def double_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    x = mx.nd.array(np.arange(8, dtype=np.float32), ctx=mx.tpu())
    (out,) = mx.rtc.PallasKernel("double", double_kernel).push(
        [x], out_shapes=[(8,)])
    err = _max_err(out.asnumpy(), np.arange(8) * 2.0)
    return {"max_err": err, "ok": err == 0.0}


def phase_kernels(size, seed, on_tpu):
    from mxnet_tpu import utils

    res = {"flash_forward": _kernel_flash(size["flash"], seed)}
    for kv in ("float32", "int8"):
        res[f"paged_{kv}"] = _kernel_paged(
            size["decoder"], size["serve"], kv, seed)
    res["rtc_pallas_kernel"] = _kernel_rtc()
    checks = {name: bool(rec["ok"]) for name, rec in res.items()}
    checks["compiled_not_interpreted"] = (
        not utils.pallas_interpret()
        and all(rec.get("tpu_custom_call", True) for rec in res.values()))
    return {"checks": checks, "kernels": res}


# ------------------------------------------------------------- four chips
def _losses_agree(want, got, rtol, atol):
    return len(want) == len(got) and all(
        abs(a - b) <= rtol * abs(a) + atol for a, b in zip(want, got))


def _device_sets_ok(arrays, n):
    return all(len(a.sharding.device_set) == n for a in arrays)


def _four_resnet(size, seed):
    """(a) Module(context=[tpu(0..3)], kvstore="tpu"): the ResNet step
    data-parallel over four contexts, global batch unchanged, against
    the same steps on one of the chips."""
    import numpy as np

    import mxnet_tpu as mx

    cfg, steps = size["resnet"], 4
    _, one = _resnet_fit(cfg, seed, mx.tpu(0), steps)
    gc.collect()
    mod, four = _resnet_fit(cfg, seed, [mx.tpu(i) for i in range(4)],
                            steps)
    fused = mod._fused_step
    rs = np.random.RandomState(seed)
    side, batch = cfg["image"], cfg["batch"]
    placed = fused._place_data({
        "data": rs.uniform(-1, 1, (batch, side, side, 3))
        .astype("float32"),
        "softmax_label": np.zeros((batch,), "float32")})
    text = fused._compiled.as_text() if fused._compiled else ""
    checks = {
        "dp_fused_mesh_built": fused._mesh is not None
        and fused._mesh.devices.size == 4,
        "dp_loss_agrees": len(four) == steps and _losses_agree(
            one, four, DP_LOSS_RTOL, DP_LOSS_ATOL),
        "dp_params_on_four_devices":
            _device_sets_ok(fused.params.values(), 4),
        "dp_batch_sharded_over_four": all(
            len(a.sharding.device_set) == 4
            and not a.sharding.is_fully_replicated
            for a in placed.values()),
        "dp_step_has_all_reduce": "all-reduce" in text,
    }
    del mod, fused, placed
    return checks, {"losses_one_chip": [round(x, 4) for x in one],
                    "losses_four_chips": [round(x, 4) for x in four],
                    "tolerance": {"rtol": DP_LOSS_RTOL,
                                  "atol": DP_LOSS_ATOL},
                    "batch_spec": "P('data')", "param_spec": "P()"}


def _transformer_steps(cfg, seed, plan, steps=2):
    """`steps` train steps of models/transformer.py driven by
    forward_backward/update, as tests/test_sharding.py does (fit's
    device metrics are not plan-aware), bf16 compute. Returns (module,
    [mean-squared-error loss per step])."""
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models.transformer import get_transformer

    net = get_transformer(
        d_model=cfg["d_model"], num_heads=cfg["num_heads"],
        d_ff=cfg["d_ff"], num_layers=cfg["num_layers"], causal=True)
    shape = (cfg["batch"], cfg["seq"], cfg["d_model"])
    rs = np.random.RandomState(seed)
    x = rs.standard_normal(shape).astype("float32")
    y = rs.standard_normal(shape).astype("float32")
    mx.random.seed(seed)
    np.random.seed(seed)
    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("label",), context=mx.tpu(0),
                        sharding=plan)
    mod.bind(data_shapes=[("data", shape)],
             label_shapes=[("label", shape)])
    mod.init_params(mx.initializer.Xavier(magnitude=1.0))
    mod.init_optimizer(kvstore="tpu", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01})
    mod.cast_compute(jnp.bfloat16)
    batch = mx.io.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)])
    losses = []
    for _ in range(steps):
        mod.forward_backward(batch)
        mod.update()
        out = mod.get_outputs()[0].asnumpy().astype("float32")
        losses.append(float(np.mean(np.square(out - y))))
    return mod, losses


def _four_plan(size, seed):
    """(b) Module(sharding=ShardingPlan(data x tensor = 2 x 2)) against
    the unsharded step."""
    import numpy as np

    from mxnet_tpu.sharding import ShardingPlan

    cfg = size["transformer"]
    mod, base = _transformer_steps(cfg, seed, None)
    del mod
    gc.collect()
    plan = ShardingPlan({"data": 2, "tp": 2})
    mod, sharded = _transformer_steps(cfg, seed, plan)
    fused = mod._fused_step
    want = plan.resolve({n: tuple(v.shape)
                         for n, v in fused.params.items()})
    shape = (cfg["batch"], cfg["seq"], cfg["d_model"])
    placed = fused._place_data({
        "data": np.zeros(shape, "float32"),
        "label": np.zeros(shape, "float32")})
    text = fused._compiled.as_text() if fused._compiled else ""
    n_tp = sum("tp" in str(v.sharding.spec)
               for v in fused.params.values())
    checks = {
        "plan_mesh_is_2x2": fused._mesh is not None
        and dict(fused._mesh.shape) == {"data": 2, "tp": 2},
        "plan_loss_agrees": _losses_agree(
            base, sharded, PLAN_LOSS_RTOL, PLAN_LOSS_ATOL),
        "plan_params_have_their_spec": all(
            v.sharding.spec == want[n] for n, v in fused.params.items()),
        "plan_params_on_four_devices":
            _device_sets_ok(fused.params.values(), 4),
        # qkv, attention out and ffn up of every layer (the default
        # rule table keeps ffn down off the tensor axis)
        "plan_weights_tensor_sharded": n_tp == 3 * cfg["num_layers"],
        "plan_batch_on_four_devices":
            _device_sets_ok(placed.values(), 4),
        "plan_step_has_all_reduce": "all-reduce" in text,
    }
    del mod, fused, placed
    return checks, {"losses_unsharded": [round(x, 5) for x in base],
                    "losses_2x2": [round(x, 5) for x in sharded],
                    "tolerance": {"rtol": PLAN_LOSS_RTOL,
                                  "atol": PLAN_LOSS_ATOL},
                    "tensor_sharded_params": n_tp}


def phase_four_chips(size, seed):
    import jax

    checks, detail = {}, {}
    for name, fn in (("resnet_dp", _four_resnet),
                     ("transformer_plan", _four_plan)):
        c, d = fn(size, seed)
        checks.update(c)
        detail[name] = d
        gc.collect()
    peaks = [_peak_bytes(d) for d in jax.devices()[:4]]
    checks["every_device_used_memory"] = all(p for p in peaks)
    return {"checks": checks, "peak_bytes_in_use": peaks, **detail}


# ------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--size", choices=sorted(SIZES), default="full",
                    help="tiny = the CPU rehearsal (never ok)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the four-chip phase and no other")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    on_tpu = dev.platform == "tpu"
    if not on_tpu and args.size != "tiny":
        sys.stderr.write(
            f"chip_smoke: needs a TPU, jax found {device}; "
            "`--size tiny` is the CPU rehearsal\n")
        return 2
    if device["count"] < args.chips:
        sys.stderr.write(
            f"chip_smoke: --chips {args.chips} on {device}\n")
        return 2

    from mxnet_tpu.exec_cache_disk import place_jax_cache

    cache_dir = place_jax_cache()
    events = _JaxEvents()
    size = SIZES[args.size]
    _emit({"phase": "start", "size": args.size, "chips": args.chips,
           "seed": args.seed, "device": device, "jax": jax.__version__,
           "jax_cache_dir": cache_dir})
    if args.chips == 4:
        oks = [_run_phase("four_chips", events, phase_four_chips, size,
                          args.seed)]
    else:
        oks = [
            _run_phase("train", events, phase_train, size, args.seed),
            _run_phase("serve", events, phase_serve, size, args.seed),
            _run_phase("kernels", events, phase_kernels, size,
                       args.seed, on_tpu),
        ]
    ok = on_tpu and all(oks)
    _emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
