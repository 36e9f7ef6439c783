"""Runner for configurations of kind `serve_window_mixed`: a decoder
whose layers mix sliding-window and full attention, each kind with its
own KV heads and its own page group, holding one chip's share of its
experts and vocabulary, served through the SAME
`ModelServer.load_decoder` / `submit_decode`, clients and window as
`kind_serve_decoder` (imported from it). Of its own it has the
configuration object, the class plan, the sample (the window's longest
row, which is of a class whose requests outlast the run, with the
tokens served to it by the window's end, and a seeded finished row),
what the run prints beside the decoder kind's counts (the classes'
prefills and finished requests inside the window, both groups' pages,
the pages the window group got back, the routing counters) and the two
numbers of its check, which are `kind_serve_sparse_latent`'s:
`served_logit_gap`, the widest gap over the checked served tokens (with
routed experts a rare near-tie at an expert's eighth place sets it, in
the program and in a float8 control alike: it tells a gross fault), and
`served_logit_gap_p90`, which rounding, a sink left out or a window
left open move in proportion.
"""
import os

import numpy as np

from perfbench.harness import check, common, trace_reduce, traffic_classes
from perfbench.harness.kind_serve_decoder import (
    MODEL, now, serve_window, window_account, window_numbers)
from perfbench.harness.kind_serve_sparse_latent import (
    _quantiles, gap_numbers)

QS = (0.5, 0.9, 0.99, 1.0)


def config_object(cfg):
    """The program's configuration object from the file's published
    keys: nothing here but the renaming, and the two layer patterns cut
    to the layers that are served."""
    from mxnet_tpu import decoding as dec

    n = int(cfg["num_hidden_layers"])
    return dec.WindowMixedConfig(
        vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        head_dim=int(cfg["head_dim"]), v_head_dim=int(cfg["v_head_dim"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        window_kv_heads=int(cfg["swa_num_key_value_heads"]),
        window=int(cfg["sliding_window"]),
        layer_pattern=tuple(int(x) for x in
                            cfg["hybrid_layer_pattern"][:n]),
        expert_layers=tuple(int(x) for x in cfg["moe_layer_freq"][:n]),
        rotary_dim=int(int(cfg["head_dim"])
                       * float(cfg["partial_rotary_factor"])),
        rope_theta=float(cfg["rope_theta"]),
        window_rope_theta=float(cfg["swa_rope_theta"]),
        value_scale=float(cfg["attention_value_scale"]),
        d_ff=int(cfg["intermediate_size"]),
        d_expert=int(cfg["moe_intermediate_size"]),
        n_experts=int(cfg["n_routed_experts"]),
        experts_held=(int(cfg["experts_held_first"]),
                      int(cfg["n_routed_experts_held"])),
        experts_per_token=int(cfg["num_experts_per_tok"]),
        rms_eps=float(cfg["layernorm_epsilon"]),
        max_len=int(cfg["max_position_embeddings"]), eos_id=-1,
        prefill_chunk=int(cfg["prefill_chunk"]))


def load_server(ctx, params, hooks=None):
    from mxnet_tpu import serving

    cfg, eng = ctx.config, ctx.traffic["engine"]
    server = serving.ModelServer()
    model = server.load_decoder(
        MODEL, params, config_object(cfg), max_batch=int(eng["max_batch"]),
        page_size=int(cfg["page_size"]),
        num_pages=tuple(int(n) for n in eng["num_pages"]),
        page_buckets=tuple(eng["page_buckets"]),
        chunk_buckets=tuple(eng["chunk_buckets"]),
        prefix_cache=bool(eng["prefix_cache"]), kv_dtype=cfg["kv_dtype"],
        max_tokens=max(int(c["output_tokens"]["max"])
                       for c in ctx.traffic["classes"]),
        run_ahead=int(eng.get("run_ahead", 0)))
    if hooks and "after_load" in hooks:
        hooks["after_load"](model)
    return server, model


def served_by(r, t1):
    return [t for t, s in zip(r.tokens, r.stamps) if s <= t1] or r.tokens


def pick_sample(ctx, touched, t1):
    """The window's longest row (prompt and the tokens served to it by
    the close; finished or not) first, then a seeded choice among the
    requests that finished inside the window."""
    rs = np.random.RandomState((ctx.seed * 31 + 17) % (2 ** 32))
    cand = [r for r in touched if r.tokens and r.error is None]
    if not cand:
        return []
    longest = max(cand, key=lambda r: len(r.prompt) + len(served_by(r, t1)))
    rest = [r for r in cand if r is not longest and r.done
            and r.stamps[-1] <= t1] or [r for r in cand if r is not longest]
    order = rs.permutation(len(rest))
    n = int(ctx.traffic["check_requests"])
    return [longest] + [rest[i] for i in order[:max(0, n - 1)]]


def score_requests(ctx, ref, params, sample, t1, controls=(),
                   control_requests=None):
    """The reference's gaps of every checked request's served tokens,
    pooled: (the program's numbers, {control: its numbers}, served
    tokens checked). The controls (one more pass each) are computed for
    the first `control_requests` of the sample, the longest first."""
    gaps, lows = [], {c: [] for c in controls}
    for i, r in enumerate(sample):
        served = served_by(r, t1)
        want = tuple(controls) if control_requests is None \
            or i < control_requests else ()
        gap, low = ref.served_gaps(params, r.prompt, served, ctx.config,
                                   control=want or False)
        gaps.append(gap)
        for c in want:
            lows[c].append(low[c] if isinstance(low, dict) else low)
        ctx.log("gaps of %d served tokens after %d (median, 90th, 99th, "
                "max): program %s%s" % (
                    len(served), len(r.prompt), _quantiles(gap, QS),
                    "".join(f"; {c} {_quantiles(lows[c][-1], QS)}"
                            for c in want)))
    pooled = np.concatenate(gaps) if gaps else np.zeros((0,))
    return (gap_numbers(pooled) if len(pooled) else {},
            {c: gap_numbers(np.concatenate(v)) for c, v in lows.items()
             if v}, len(pooled))


def class_counts(plan, clients, t0, t1):
    """Per class: the requests that finished, and those whose prompt was
    prefilled (their first token came), inside the window. A long-class
    row does neither: its answer outlasts set-up and window."""
    out = {}
    for c in plan.mix["classes"]:
        mine = [r for cl in clients if plan.klass(cl.idx) == c["name"]
                for r in cl.requests if r.stamps]
        out[c["name"] + "_finished_in_window"] = sum(
            1 for r in mine if r.done and t0 <= r.stamps[-1] <= t1)
        out[c["name"] + "_prefilled_in_window"] = sum(
            1 for r in mine if t0 <= r.stamps[0] <= t1)
    return out


def run(ctx, hooks=None):
    import jax.numpy as jnp

    ref = ctx.reference()
    cfg = ctx.config
    config_object(cfg)      # a program without the block fails here, at once
    params = ref.make_params(ctx.seed, cfg, jnp.dtype(cfg["weights_dtype"]))
    plan = traffic_classes.ClassPlan(ctx.traffic, ctx.seed,
                                     cfg["vocab_size"])
    server, model = load_server(ctx, params, hooks)
    ctx.log("decoder loaded and warm")
    t_loaded = now()
    try:
        clients, w = serve_window(ctx, server, model, plan)
        pool = model.stats.snapshot()
    finally:
        server.unload(MODEL)      # stops the scheduler, drops its stats
        server.stop(drain=False)
    t0, t1 = w["t0"], w["t1"]
    tokens, gaps, ttft, touched, failed = window_numbers(clients, t0, t1)
    sample = pick_sample(ctx, touched, t1)
    rate = tokens / (t1 - t0)
    peak = common.peak_bytes(ctx.devices)
    ctx.log(f"memory_stats: {ctx.devices[0].memory_stats()}")
    names = ("steps", "decode_tokens", "prefill_tokens", "prefills",
             "preemptions", "completed", "prefill_chunks", "expert_rows",
             "experts_hit", "ctx_tokens", "window_tokens",
             "window_pages_released")
    d = {k: w["stats1"].get(k, 0) - w["stats0"].get(k, 0) for k in names}
    held = [b - a for a, b in zip(w["stats0"].get("pages_held") or [0, 0],
                                  w["stats1"].get("pages_held") or [0, 0])]
    steps = sorted((s[2] - s[1]) * 1e3 for s in w["spans"]
                   if s[0] == "decoding.step" and s[1] >= t0 and s[2] <= t1)
    # set-up's parts: what was prefilled before the window opened (the
    # long prompts' chunks nearly all of it), from the stats at its
    # opening: the span ring is cleared there
    s0 = w["stats0"]
    early_s = s0["prefill_tokens"] / s0["prefill_tokens_per_s"] \
        if s0.get("prefill_tokens_per_s") else 0.0
    counts = {
        "requests_in_window": len(touched),
        "requests_finished": sum(1 for r in touched if r.done),
        "tokens": tokens, "token_gaps": len(gaps),
        "fenced_seconds": t1 - t0, "seconds_asked": ctx.seconds,
        "compilations_in_window": w["built"],
        "traces_since_warmup": w["stats1"].get("traces_since_warmup"),
        "peak_bytes": peak, "engine_steps": d["steps"],
        "decode_tokens": d["decode_tokens"],
        "prefill_tokens": d["prefill_tokens"], "prefills": d["prefills"],
        "prefill_chunks": d["prefill_chunks"],
        "preemptions": d["preemptions"],
        "preemptions_since_load": w["stats1"].get("preemptions"),
        **class_counts(plan, clients, t0, t1),
        "pages_free_low_watermark": pool.get("free_low_watermark"),
        "window_pages_free_low_watermark": pool.get(
            "window_free_low_watermark"),
        "pages_held_mean": [round(h / d["steps"], 1) for h in held]
        if d["steps"] else None,
        "window_pages_released": d["window_pages_released"],
        "ctx_tokens_per_step": round(d["ctx_tokens"] / d["steps"], 1)
        if d["steps"] else None,
        "window_tokens_per_step": round(d["window_tokens"] / d["steps"], 1)
        if d["steps"] else None,
        "expert_rows": d["expert_rows"], "experts_hit": d["experts_hit"],
        "expert_rows_max": w["stats1"].get("expert_rows_max"),
        "step_ms_median": steps[len(steps) // 2] if steps else None,
        "step_ms_longest": [round(x, 2) for x in steps[-5:][::-1]],
        "generate_throughput": rate,
        # not an end-to-end metric of this kind's cells (one turn in
        # seven holds a short prefill of 32-1024 tokens, so the 95th
        # percentile reads the prefills' length distribution)
        "tpot_p95_ms": common.quantile(gaps, 0.95) * 1e3 if gaps else None,
        "tpot_p50_ms": common.quantile(gaps, 0.5) * 1e3 if gaps else None,
        "setup_s": t0 - common.T_PROCESS_START,
        "setup_load_and_warm_s": round(t_loaded - common.T_PROCESS_START, 1),
        "setup_first_tokens_s": round(t0 - t_loaded, 1),
        "setup_prefill_s": round(early_s, 1),
        "setup_prefill_tokens": s0["prefill_tokens"],
        "setup_prefill_chunks": s0.get("prefill_chunks"),
    }
    counts.update(w["gc"])
    counts.update(window_account(w["spans"], t0, t1))
    ctx.log("window: " + str(counts))
    # free the program's state before the reference runs
    del model, server
    for c in clients:
        c.server = c.current = None
    common.free_device_memory()
    t_ref = now()
    controls = (hooks or {}).get("control") or ()
    controls = ("fp8",) if controls is True else \
        (controls,) if isinstance(controls, str) else tuple(controls)
    numbers, low, n_tok = score_requests(
        ctx, ref, params, sample, t1, controls,
        int((hooks or {}).get("control_requests", len(sample))))
    ctx.log(f"reference: {now() - t_ref:.1f}s over {len(sample)} requests, "
            f"{n_tok} served tokens")
    if not numbers:
        numbers = {k: float("nan") for k in cfg["check"]["limits"]}
    checks, ok = check.judge(numbers, cfg["check"]["limits"])
    ok = ok and bool(sample) and failed == 0 and w["built"] == 0
    e2e = {"generate_throughput": rate, "setup_s": counts["setup_s"]}
    res = {"correct": ok, "attempted": len(touched), "failed": failed,
           "counts": counts, "checks": checks, "end_to_end": e2e,
           "control_gap": low or None, "served_tokens_checked": n_tok,
           "device": dict(ctx.device, memory_peak_bytes=peak)}
    if ctx.trace:
        t_red = now()
        raw = trace_reduce.load_xplane(os.path.join(ctx.out_dir, "trace"))
        red = trace_reduce.Reduced(raw, w["spans"], w["t_open"],
                                   w["t_close"])
        if not red.ok:
            raise RuntimeError("the traced window holds no device "
                               "operation: nothing to reduce")
        lo, hi = w["t_open"], w["t_close"]
        facts = {"config": cfg, "chips": ctx.chips, "peaks": ctx.peaks,
                 "trace": red, "spans": w["spans"], "window_host": (lo, hi),
                 "counters": d, "ttft": ttft, "all_gaps": gaps,
                 "tokens": window_numbers(clients, lo, hi)[0]}
        t_read = now()
        res["per_layer"] = common.read_per_layer(ctx, facts)
        for k, v in facts.get("notes", {}).items():
            ctx.log(f"{k}: {v}")
        t_brk = now()
        res["breakdown"] = red.breakdown()
        res["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        ctx.log(f"trace: window {red.window_s:.3f}s busy {red.busy_s:.3f}s "
                f"clock drift {red.drift * 1e3:.3f}ms; reading it took "
                f"{t_read - t_red:.1f}s, the readers {t_brk - t_read:.1f}s, "
                f"the breakdown {now() - t_brk:.1f}s")
    return res
